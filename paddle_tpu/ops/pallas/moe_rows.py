"""A tile's rows added into a float32 [tokens, width] accumulator by row
DMAs: the add-back of the dropless mixture's tile loops
(`incubate/distributed/models/moe/dropless.py`).

XLA's scatter-add moves a 512-row tile of a `[T, K]` float32 array at
280-290 ns a row (measured on a v5e at K 2,048 and 2,304), where a row
streams in 20-34 ns: the array's tiled layout (8 rows by 128 lanes) has
no single row to address. The view `[T, 1, K]` has: XLA lays it out a row
at a time (`T(1,128)`) and a row is one contiguous copy. So the
accumulator is born in that form, rides the loop in it, and

  `add_rows(acc, idx, y, n_real)` adds `y[r]` to row `idx[r]` of `acc`
  for r < n_real, in place (`input_output_aliases`): the rows copied into
  VMEM, one asynchronous copy a row, all started before the first is
  waited for and all waited for before a row is touched; then each row
  added and its copy back started; every copy back waited for before the
  kernel ends, so the next tile reads what this one wrote. 50-60 ns a row,
  the two copies' issue.

**Rows from `n_real` on are never read or written**: a padding row names
token 0, and under read-modify-write its copy back would race with token
0's own row in the same tile. The caller promises that idx[:n_real] are
distinct (a tile is one expert's, a token picks an expert once), so no
two copies of a call meet.

The gather of a tile's rows stays XLA's: it moves a row of `[T, K]` in 29
ns, and a row-DMA gather out of such a view (45 ns a row from HBM to HBM,
and 1.15 ms to make the view) lost to it on the chip (PERF.md, PR 36).

`row_adds` is how a loop gets the accumulator's three moves: the kernel on
TPU for a width it takes, XLA's scatter-add on `[T, K]` itself on CPU and
for any other width (counted in `routing.xla_fallbacks`).
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import routing
from .flash_attention import _LANES, pl, pltpu

__all__ = ["RowAdds", "row_adds", "add_rows", "supports"]

F32 = jnp.float32
# rows a trip of a copy loop (one that only starts copies: 45 ns a row at 8,
# 46 at 4, 59 at 1, on a v5e)
_UNROLL = 8
# scoped VMEM `add_rows` asks: the accumulator's tile and the tile added
# to it, and room for the compiler's own
_VMEM_SLACK = 2 << 20


def _add_rows_vmem(tile: int, width: int) -> int:
    return 2 * tile * width * 4 + _VMEM_SLACK


def supports(width: int, tile: int) -> bool:
    """Does `add_rows` take `tile`-row tiles of a `[T, width]` accumulator?
    A row is whole lanes and the two tiles fit a v5e's VMEM."""
    return width % _LANES == 0 and _add_rows_vmem(tile, width) <= 14 << 20


def _each_row(n, body):
    """body(r) for r in [0, n), `_UNROLL` rows a trip (Mosaic unrolls a
    loop wholly or not at all); n a traced int32."""
    def trip(g, c):
        for j in range(_UNROLL):
            body(g * _UNROLL + j)
        return c

    whole = jax.lax.div(n, _UNROLL)
    jax.lax.fori_loop(0, whole, trip, 0)
    jax.lax.fori_loop(whole * _UNROLL, n, lambda r, c: (body(r), c)[1], 0)


def _add_kernel(idx_ref, n_ref, acc_ref, y_ref, out_ref, rows, add, sem):
    del acc_ref                       # out_ref is the same array
    n = n_ref[0]
    tile = pltpu.make_async_copy(y_ref, add, sem.at[0])
    tile.start()

    def read(r):
        return pltpu.make_async_copy(out_ref.at[idx_ref[r]], rows.at[r],
                                     sem.at[1])

    def write(r):
        return pltpu.make_async_copy(rows.at[r], out_ref.at[idx_ref[r]],
                                     sem.at[2])

    def plus(r):
        rows[r] = rows[r] + add[r]
        write(r).start()

    _each_row(n, lambda r: read(r).start())
    # the reads share a semaphore, so one wait does not say WHICH row has
    # landed: all of them, before the first row is touched
    _each_row(n, lambda r: read(r).wait())
    tile.wait()
    _each_row(n, plus)
    _each_row(n, lambda r: write(r).wait())


@functools.partial(jax.jit, static_argnames=("interpret",))
def add_rows(acc, idx, y, n_real, interpret=False):
    """acc float32 [T, 1, K] with y[r] added to row idx[r] for r < n_real,
    in place. idx int32 [tile] (distinct below n_real), y float32
    [tile, K], n_real an int32 scalar."""
    tile, k = y.shape
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return routing.pallas_call(
        _add_kernel,
        name="moe_add_rows",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,), in_specs=[hbm, hbm],
            out_specs=hbm,
            scratch_shapes=[pltpu.VMEM((tile, 1, k), F32),
                            pltpu.VMEM((tile, 1, k), F32),
                            pltpu.SemaphoreType.DMA((3,))]),
        out_shape=jax.ShapeDtypeStruct(acc.shape, F32),
        # operands: idx, n_real, acc, y -> the accumulator is the output
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_add_rows_vmem(tile, k)),
        interpret=interpret,
    )(idx, jnp.reshape(n_real, (1,)).astype(jnp.int32), acc,
      y.reshape(tile, 1, k))


_alias_checked: set = set()


def _alias_selfcheck(width, tile):
    """One-time (per geometry, per process) on-device check of the aliased
    accumulator riding a loop, as the other accumulating kernels have: two
    tiles on the same tokens, token 0 among them, the first tile padded
    with rows that name token 0, against the sums formed on the host. A
    second tile that read before the first had written, or a padding row
    written, is off by a whole addend and raises."""
    from ...utils import flags as _flags

    key = (width, tile)
    if key in _alias_checked or not _flags.get_flag(
            "FLAGS_pallas_alias_selfcheck"):
        return
    rng = np.random.default_rng(0)
    acc = rng.standard_normal((2 * tile, width)).astype(np.float32)
    y = rng.standard_normal((2, tile, width)).astype(np.float32)
    tokens, n_real = 2 * np.arange(tile), np.array([tile - 3, tile], np.int32)
    idx = np.stack([np.where(np.arange(tile) < n_real[0], tokens, 0),
                    tokens]).astype(np.int32)
    want = acc.copy()
    want[tokens[:n_real[0]]] += y[0, :n_real[0]]
    want[tokens] += y[1]

    def _run():
        got = jax.jit(lambda a, idx, y, n: jax.lax.fori_loop(
            0, 2, lambda i, a: add_rows(a, idx[i], y[i], n[i]),
            a.reshape(2 * tile, 1, width)))(acc, idx, y, n_real)
        return float(np.max(np.abs(np.asarray(got).reshape(want.shape)
                                   - want)))

    # run eagerly even when tracing (fresh thread has no trace context)
    import concurrent.futures
    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        err = pool.submit(_run).result()
    if not err < 1e-5:
        raise RuntimeError(
            f"moe_add_rows self-check FAILED (max err {err:.3e}, width "
            f"{width}, tile {tile}): the aliased accumulator's rows no "
            "longer hold every tile's sum; report this.")
    _alias_checked.add(key)   # only memoize a PASSING check


# A loop's float32 accumulator over [T, K]: `zeros((T, K))` is the form it
# is born in and rides the loop in, `add(acc, idx, y, n_real)` adds the
# tile y [len(idx), K] at the rows idx names, `whole(acc)` is it as [T, K].
RowAdds = collections.namedtuple("RowAdds", "zeros add whole")

_XLA = RowAdds(zeros=lambda shape: jnp.zeros(shape, F32),
               add=lambda acc, idx, y, n_real: acc.at[idx].add(y),
               whole=lambda acc: acc)


def row_adds(width, tile, interpret=None) -> RowAdds:
    """The accumulator of a loop over `tile`-row tiles of `[T, width]`
    (module docstring), by `routing.route`."""
    use_kernel, interpret = routing.route(
        "moe_add_rows", supports(width, tile),
        (f"width{width}", f"tile{tile}"), interpret)
    if not use_kernel:
        return _XLA
    if not interpret:
        _alias_selfcheck(width, tile)
    return RowAdds(
        zeros=lambda shape: jnp.zeros((shape[0], 1, width), F32),
        add=functools.partial(add_rows, interpret=interpret),
        whole=lambda acc: acc.reshape(acc.shape[0], width))
