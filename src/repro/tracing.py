"""Host spans of the serving program in the profiler's trace.

`span(name, **args)` opens a `jax.profiler.TraceAnnotation` named
`moebius.<name>`. It writes into the profiler's own trace, on the same
clock as the device ops, so each gap in which the chip is idle lines up
with the program phase the host was in. Outside a profiler session it
records nothing and costs well under a microsecond, so the spans stay in
the code. Numbers go into keyword arguments, never into the name;
arguments known only at the end of a span are added with
`set_metadata(**args)` on the object the `with` statement binds.

The step's span tree (DESIGN.md §9):

    moebius.step {step, B, Sq, dec, pre, passes, overlap}
      moebius.sched.admit         admission, deadline expiry, prefill starts
      moebius.policy              queue snapshot + coordinator
      moebius.switch {direction, bytes_sent}  a live switch, when taken
        moebius.switch.plan       plan and stage (decode paused)
        moebius.switch.chunk {i}  one layer chunk; overlap decode beside it
        moebius.switch.commit     dirty-page delta + commit (decode paused)
      moebius.sched.plan          the step's dispatch plan
      moebius.exec.copies         copy-on-write page copies
      moebius.exec.stage {B, Sq, dec, pre, slots}  host buffers + uploads
      moebius.exec.launch         the jitted step call (compiles show here)
      moebius.exec.fetch          host blocked on the previous dispatch's
                                  sampled tokens
      moebius.exec.fused          one fused N-step decode dispatch
      moebius.sched.commit        those tokens into requests
      moebius.exec.drain {cause}  lands the dispatch in flight (its
                                  exec.fetch + sched.commit inside)
      moebius.account             per-step bookkeeping

The mixed step is pipelined one deep: a step launches its own dispatch,
then fetches and commits the one launched before it (`overlap` 1), or
launches with nothing in flight (`overlap` 0). `step`'s B, Sq, dec and
pre are its launched dispatch's (0 when it launched none); the decode
steps between a switch's chunks carry theirs on `exec.stage`. `passes`
is the expert weight passes the grouped GEMM made in the dispatches the
step fetched (`ServeMetrics.moe_weight_passes`). A drain can also open
inside `sched.plan` (before a preemption or truncation), `switch` or
`sched.admit` (cancel, deadline, fault).

The switch spans sit at the bounds of `SwitchStats`' timers, so plan +
commit is the switch's `pause_s`; a monolithic switch has no chunks and
its whole migration is the commit. `bytes_sent`, set when the switch
commits, is its `SwitchRecord.bytes_sent`.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

PREFIX = "moebius."


def span(name: str, **args) -> TraceAnnotation:
    return TraceAnnotation(PREFIX + name, **args)
