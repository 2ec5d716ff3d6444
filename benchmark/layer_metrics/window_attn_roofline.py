"""The `window_attention` scope: least time for the band's pairs
(kernels/window_attention.py) over the scope's traced time. Printed
beside it: the score entries the kernel forms over the band's pairs."""
from harness import attention_scopes
from kernels import window_attention


def read(ctx):
    share = attention_scopes.share(ctx, "window_attention",
                                   "window_attention")
    if share is not None:
        from paddle_tpu.ops.pallas.splash_attention import computed_pairs

        c, seq = ctx["cell"]["config"], ctx["cell"]["traffic"]["seq"]
        window = c["sliding_window"]
        print("window_attn_roofline: computed_pairs(window) / the band's "
              f"pairs = {computed_pairs(seq, window=window)} / "
              f"{window_attention.band_pairs(seq, window)} = "
              f"{computed_pairs(seq, window=window) / window_attention.band_pairs(seq, window):.3f}",
              flush=True)
    return share
