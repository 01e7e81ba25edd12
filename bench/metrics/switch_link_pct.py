"""Switch: share of the link roofline (%). For each switch in the window,
the least time its bytes could take on the busiest chip's link (the
owner-changed bytes that chip must send over `ici_bits_per_s` / 8) over
the switch's `total_s`; the mean over the window's switches.

The bytes are counted here from the configuration file, independent of
the program. The cell's mesh is 1 x G (G = the cell's chips) and both
layouts are pure: TP holds every expert's I/G width columns on each chip,
EP holds E/G whole experts on each chip (G divides E).

Experts. A cell (expert e, width column i) of a layer is 3 * D elements:
its gate and up rows of w13 and its column of w2. A chip holds E * I / G
cells in either layout, and keeps of them in the other layout the cells
of its own E/G experts in its own I/G columns, (E/G) * (I/G). The rest
changes owner, in either direction, and the chip sends it:

    E * I * (G - 1) / G^2 * 3 * D * L * bytes per element.

KV pages. A page holds, in each of L layers, K and V for `page_size`
positions of K heads of `head_dim`. In TP each chip holds Kl = K/G of the
heads of every page (one, repeated, where K < G), in EP the page's owner
holds all K. tp->ep the owner lacks K - Kl heads, which other chips send;
ep->tp the owner sends each of the G - 1 others its Kl heads. With K >= G,
as in Mixtral, both are K * (G - 1) / G heads a page. The pages, spread
over the G chips by the planner's balance, give each chip

    ceil(pages * heads * L * 2 * page_size * head_dim * bytes / G)

the page count being the switch's `kv_pages`: the pages the plan moves.
The window's record does not carry the commit's re-copy of the pages that
decode dirtied in the switch (a page or two per live request, under a
thousandth of the bytes here), so the share leaves it out.
"""

BYTES = {"bfloat16": 2, "float32": 4}


def link_bytes(conf: dict, chips: int, direction: str, pages: int) -> int:
    """Owner-changed bytes the busiest chip sends in a switch `direction`
    ("tp_to_ep" or "ep_to_tp") of the configuration `conf` on a 1 x `chips`
    mesh that moves `pages` KV pages (the derivation is the module's
    docstring)."""
    G = chips
    E = conf.get("num_local_experts") or conf["num_experts"]
    I = conf.get("moe_intermediate_size") or conf["intermediate_size"]
    D, L, K = (conf["hidden_size"], conf["num_hidden_layers"],
               conf["num_key_value_heads"])
    dh = conf.get("head_dim") or D // conf["num_attention_heads"]
    b = BYTES[conf["torch_dtype"]]
    experts = E * I * (G - 1) * 3 * D * L * b // G**2
    kl = max(1, K // G)
    heads = K - kl if direction == "tp_to_ep" else (G - 1) * kl
    kv = -(-pages * heads * L * 2 * conf["engine"]["page_size"] * dh * b
           // G)
    return experts + kv


def read(run):
    s = run.window.switches
    if not s:
        return None
    link = run.peaks["ici_bits_per_s"] / 8
    return sum(
        link_bytes(run.conf, run.chips, x["direction"], x["kv_pages"])
        / link / x["total_s"] for x in s) / len(s) * 100.0
