"""paged_attention_chunk: a window of c prompt tokens per sequence over
the sequence's cache so far plus the window itself (causal inside it)."""


def cost(starts, c, heads, d, itemsize=2):
    """`starts`: cached tokens before the window, one per sequence."""
    pairs = sum(c * s + c * (c + 1) // 2 for s in starts)
    ops = 4 * d * heads * pairs
    nbytes = sum(2 * (s + c) * heads * d * itemsize for s in starts) \
        + 2 * len(starts) * c * heads * d * itemsize
    return ops, nbytes
