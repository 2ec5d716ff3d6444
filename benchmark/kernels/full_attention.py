"""The `full_attention` scope: causal attention over every earlier key,
forward once and backward once, counted as kernels/window_attention.py
counts a sliding layer (4d forward + 10d backward a head and causal
pair; recompute's second forward and the pairs a tile forms above the
diagonal not required)."""
from kernels import window_attention


KIND = "full_attention"


def layers(cell) -> int:
    return window_attention.layers(cell, KIND)


def from_cell(cell, ctx=None):
    """One full LAYER's cost at the cell's shapes."""
    c, job = cell["config"], cell["traffic"]
    b, s = job["batch"] // cell["chips"], job["seq"]
    return window_attention.cost(
        b, s, c["num_attention_heads"], c["num_key_value_heads"],
        c["head_dim"], b * s * (s + 1) // 2)
