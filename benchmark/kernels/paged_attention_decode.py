"""paged_attention_decode: one query token per running sequence over its
cached keys and values. Bandwidth-bound: the bytes are the K and V of the
live context, whatever the kernel's grid reads."""


def cost(context_tokens, heads, d, itemsize=2, sequences=1):
    """`context_tokens`: live tokens summed over the sequences of a call."""
    ops = 4 * d * heads * context_tokens
    nbytes = 2 * context_tokens * heads * d * itemsize \
        + 2 * sequences * heads * d * itemsize
    return ops, nbytes
