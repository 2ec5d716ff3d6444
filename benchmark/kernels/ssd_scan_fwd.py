"""ssd_scan_fwd: the chunked state-space scan's forward kernel
(paddle_tpu/ops/pallas/ssd_scan.py). A chunk of Q steps requires, a group
of heads, C B^T once (2 Q^2 N) and, a head, the masked product (C B^T o L)
(dt o X) (2 Q^2 P), the read of the carried state C S (2 Q N P) and its
update B^T (w o dt o X) (2 Q N P). It reads x, B, C and the step sizes
once and writes y once; the decay matrix L, the running sums and the
state never leave the chip. The forward that per-layer recompute runs
again is not required and not counted (the reader counts a layer once)."""


def chunk_ops(q, heads, p, groups, n) -> int:
    """Operations of one chunk of `q` steps, all heads, forward."""
    return groups * 2 * q * q * n + heads * (2 * q * q * p + 4 * q * n * p)


def traffic_bytes(b, seq, heads, p, groups, n, itemsize=2) -> int:
    """x and y [b, seq, heads, p], B and C [b, seq, groups, n] in the
    operands' type, the step sizes [b, seq, heads] float32."""
    return (2 * b * seq * heads * p + 2 * b * seq * groups * n) * itemsize \
        + b * seq * heads * 4


def cost(b, seq, heads, p, groups, n, chunk, itemsize=2):
    ops = b * (seq // chunk) * chunk_ops(chunk, heads, p, groups, n)
    return ops, traffic_bytes(b, seq, heads, p, groups, n, itemsize)


def shapes(cell):
    c, job = cell["config"], cell["traffic"]
    return (job["batch"] // cell["chips"], job["seq"], c["mamba_num_heads"],
            c["mamba_head_dim"], c["n_groups"], c["ssm_state_size"],
            c["chunk_size"])


def layers(cell) -> int:
    """`M` layers a step of the cell runs."""
    c = cell["config"]
    return c["hybrid_override_pattern"][:c["num_hidden_layers"]].count("M")


def from_cell(cell, ctx=None):
    """One call's cost at the cell's shapes: one `M` layer's forward."""
    return cost(*shapes(cell))
