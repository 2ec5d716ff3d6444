"""kernels/indexer_scores_{fwd,bwd}.py by hand at Keye's widths, and from
the keye cell's own files."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))

from harness import load  # noqa: E402

T, S, HEADS, D = 512, 8192, 16, 64
PAIRS = T * (S + 1) // 2                      # 2,097,408 causal pairs
INPUTS = (HEADS * T * D + S * D + T * HEADS) * 2     # bf16


def test_forward_cost_by_hand():
    ops, nbytes = load.module("kernels", "indexer_scores_fwd").cost(
        T, S, HEADS, D)
    assert PAIRS == 2_097_408
    assert ops == 2 * 64 * 16 * PAIRS == 4_295_491_584
    assert INPUTS == 1_048_576 + 1_048_576 + 16_384
    assert nbytes == INPUTS + T * S * 4 == 18_890_752


def test_backward_cost_by_hand():
    ops, nbytes = load.module("kernels", "indexer_scores_bwd").cost(
        T, S, HEADS, D)
    assert ops == 3 * 4_295_491_584
    # the inputs read, d_scores read, the three gradients written
    assert nbytes == INPUTS + T * S * 4 + INPUTS == 21_004_288


def test_from_cell_reads_the_keye_cells_widths():
    cell = load.cell("keye-vl2-30b-a3b.train.4x8192")
    for name in ("indexer_scores_fwd", "indexer_scores_bwd"):
        mod = load.module("kernels", name)
        assert mod.from_cell(cell) == mod.cost(T, S, HEADS, D)
    metric = [m for m in cell["per_layer"]
              if m["name"] == "indexer_scores_roofline"]
    assert [m["layer"] for m in metric] == ["kernels"]
    # no trace, as at a commit without the kernels' events: nothing to read
    read = load.module("layer_metrics", "indexer_scores_roofline").read
    assert read({"trace": None}) is None
