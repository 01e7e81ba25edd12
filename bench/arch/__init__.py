"""One module per architecture of the benchmark's configurations: each
configuration file names its module under `"arch"`. What a module gives
the harness is stated in topk_moe.py's docstring."""
