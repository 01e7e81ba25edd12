"""Output tokens produced inside the window, over the window (tokens/s)."""
from benchlib import readers


def read(run):
    return readers.output_tok_s(run)
