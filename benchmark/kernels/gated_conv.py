"""gated_conv: the gated short convolution of an LFM2 `conv` layer,
y = C * conv(B * X) on the [rows, 3 h] product (paddle_tpu/ops/pallas/
gated_conv.py), whatever implements it. One pass over `rows` tokens of `h`
channels and `taps` taps REQUIRES, an element:

  forward   z = B X (1), the taps' products and sums (2 taps - 1), C c (1);
            reads B, C, X, writes y: 4 tables of the operands' type
  backward  z and c again (2 taps), dC = dy c (1), dc = dy C (1), dz by the
            taps reversed (2 taps - 1), dB and dX (2), dw's products and
            sums (2 taps); reads B, C, X, dy, writes dB, dC, dX: 7 tables

and the taps themselves (float32). The history rows a tiling reads twice and
the forward that per-layer recompute runs again are not required and not
counted (the reader counts a layer's forward once)."""


def cost(rows, h, taps, itemsize=2):
    """{"fwd" | "bwd": {"ops", "bytes"}} of one pass over [rows, 3 h]."""
    n = rows * h
    return {"fwd": {"ops": n * (2 * taps + 1),
                    "bytes": 4 * n * itemsize + 4 * taps * h},
            "bwd": {"ops": n * (6 * taps + 3),
                    "bytes": 7 * n * itemsize + 8 * taps * h}}


def shapes(cell):
    c, job = cell["config"], cell["traffic"]
    return (job["batch"] // cell["chips"] * job["seq"], c["hidden_size"],
            c["conv_L_cache"])


def layers(cell) -> int:
    """Gated-convolution layers a step of the cell runs."""
    return sum(k.startswith("conv") for k in cell["config"]["layer_kinds"])


def from_cell(cell, ctx=None, backward=False):
    """One layer-pass at the cell's shapes -> (ops, bytes)."""
    one = cost(*shapes(cell))["bwd" if backward else "fwd"]
    return one["ops"], one["bytes"]
