"""kda_fwd: the chunked gated delta rule's forward kernel
(paddle_tpu/ops/pallas/kda.py), a decay a channel. A chunk of C tokens of one
head (K keys, V values) requires: the pairs A = (beta K)(K)^T and B = Q K^T
under their decays, the lower triangle alone (2 x 2 C^2 K / 2); the pseudo-
values as one product with the inverse of the triangular system (2 C^2 V / 2:
solving it by substitution costs the same); the read of the carried state by
Q and by beta K (2 x 2 C K V); tril(B) U (2 C^2 V / 2) and the state's update
K^T U (2 C K V). How the inverse is formed (the program: ten [C, C] float32
products by Neumann doubling) is the algorithm's choice and not counted, nor
the pairs it forms above the diagonal. It reads q, k, v and the decay a
(float32, a channel) and beta once and writes o once; the running sums, the
pairs and the state never leave the chip. The forward that per-layer
recompute runs again is not required and not counted (the reader counts a
layer once)."""

CHUNK = 64


def chunk_ops(c, k, v) -> int:
    """Operations of one chunk of `c` tokens, one head, forward."""
    return 2 * c * c * k + c * c * v + 6 * c * k * v


def traffic_bytes(b, seq, heads, k, v, itemsize=2) -> int:
    """q, k [b, seq, heads, k], v and o [b, seq, heads, v] in the operands'
    type, a [b, seq, heads, k] and beta [b, seq, heads] float32."""
    return b * seq * heads * ((2 * k + 2 * v) * itemsize + 4 * k + 4)


def cost(b, seq, heads, k, v, chunk, itemsize=2):
    ops = b * (seq // chunk) * heads * chunk_ops(chunk, k, v)
    return ops, traffic_bytes(b, seq, heads, k, v, itemsize)


def shapes(cell):
    c, job = cell["config"], cell["traffic"]
    chunk = cell.get("tiling", {}).get("kda_chunk_size", CHUNK)
    return (job["batch"] // cell["chips"], job["seq"],
            c["num_attention_heads"], c["head_dim"], c["head_dim"], chunk)


def layers(cell) -> int:
    """KDA layers a step of the cell runs."""
    c = cell["config"]
    return sum((i + 1) % c["layer_group_size"] != 0
               for i in range(c["num_hidden_layers"]))


def from_cell(cell, ctx=None):
    """One call's cost at the cell's shapes: one KDA layer's forward."""
    return cost(*shapes(cell))
