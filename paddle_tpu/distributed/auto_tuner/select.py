"""Layout selection: the validated cost-model planner promoted from
validation artifact (docs/PLANNER_VALIDATION.md, Spearman 0.90 on the
host mesh) to DECISION-MAKER.

`pick_layout` enumerates (dp, mp, pp, micro) factorizations of the
device count, prunes infeasible ones with the reference pruning rules
(`prune.prune_candidates` — divisibility + HBM-fit), ranks the
survivors with `tuner.estimate_step_ms` under BACKEND-CALIBRATED
collective constants, and returns the winner plus the scan-granularity
knobs (`scan_unroll` / `layer_chunk`, fixed defaults) and the comm
bucket size. `jit.select_train_step(auto=True)` consumes
this to build the mesh + hybrid step end-to-end.

Env override (preserved per ISSUE 8): ``PADDLE_HYBRID_LAYOUT=
"dp=4,mp=2"`` (optionally ``pp=``/``micro=``) skips the planner and
forces the layout — still validated against the pruning rules so an
impossible forced layout fails loudly, not numerically.

Calibration staleness (satellite): `calibrate_backend_cached` persists
`calibrate_backend()`'s measured constants under ``.bench_live/`` keyed
by (backend platform, device count) with an invalidation hash over the
calibration code + jax version — re-measured only when missing or
stale, so planner callers stop paying the ~1s probe per process and
ad-hoc consumers stop silently mixing constants from different
toolchains.
"""
from __future__ import annotations

import json
import os

from .prune import prune_candidates
from .search import grid_candidates
from .tuner import (
    Candidate, ModelSpec, calibrate_backend, estimate_memory_gb,
    estimate_step_ms,
)

__all__ = ["pick_layout", "calibrate_backend_cached", "spec_of_model",
           "record_measured_step", "measured_steps", "layout_name",
           "LAYOUT_ENV"]

LAYOUT_ENV = "PADDLE_HYBRID_LAYOUT"


def _repo_root():
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))


def _calib_hash():
    """Invalidation hash: the calibration + cost-model code and the jax
    version. A change to either re-measures instead of reusing. Built
    on the shared fingerprint helper (ISSUE 17) — one hashing recipe
    across bench's compile-path hash, the sweep gate and this."""
    import jax

    from ...jit.compile_cache import source_fingerprint
    from . import tuner as _tuner

    return source_fingerprint(_tuner.calibrate_backend,
                              _tuner.estimate_step_ms,
                              extra=(jax.__version__,), prefix=None)


def calibrate_backend_cached(devices=None, cache_dir=None, refresh=False):
    """`tuner.calibrate_backend` behind a keyed on-disk cache.

    Key: (backend platform, device count); file:
    ``backend_calib_<platform>_<n>.json`` under ``.bench_live/``; entries
    carry the invalidation hash from `_calib_hash` and are re-measured
    when it mismatches (stale toolchain/code) or the file is unreadable.
    """
    import jax

    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    platform = devices[0].platform if devices else "none"
    n = len(devices)
    if cache_dir is None:
        cache_dir = os.path.join(_repo_root(), ".bench_live")
    path = os.path.join(cache_dir, f"backend_calib_{platform}_{n}.json")
    want = _calib_hash()
    if not refresh and os.path.exists(path):
        try:
            with open(path) as f:
                rec = json.load(f)
            if rec.get("calib_hash") == want:
                return rec["constants"]
        except (OSError, ValueError, KeyError):
            pass
    constants = calibrate_backend(devices)
    try:
        os.makedirs(cache_dir, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"calib_hash": want, "platform": platform,
                       "n_devices": n, "constants": constants}, f)
        os.replace(tmp, path)
    except OSError:
        pass                       # cache is an optimization, not truth
    return constants


def _measured_path(platform, n_devices, cache_dir=None):
    if cache_dir is None:
        cache_dir = os.path.join(_repo_root(), ".bench_live")
    return os.path.join(cache_dir,
                        f"measured_steps_{platform}_{n_devices}.json")


def layout_name(cand) -> str:
    """Canonical layout key shared by the ranking table and the
    measured-step store: ``dp4xmp2xpp1m1``."""
    return (f"dp{cand.dp}xmp{cand.mp}xpp{cand.pp}"
            f"m{cand.micro_batch}")


def record_measured_step(layout, step_ms, n_devices, platform=None,
                         cache_dir=None):
    """Feed one MEASURED per-step wall time back to the planner
    (ISSUE 17 closed loop): training loops call this so
    `pick_layout` can re-rank from live timelines instead of static
    calibration. ``layout`` is a `Candidate` or a `layout_name` string.
    Records are keyed like the backend-calib cache ((platform, n)) and
    carry the calib hash, so stale-toolchain measurements never mix
    with fresh estimates."""
    import jax

    if platform is None:
        devs = jax.devices()
        platform = devs[0].platform if devs else "none"
    name = layout if isinstance(layout, str) else layout_name(layout)
    path = _measured_path(platform, int(n_devices), cache_dir)
    recs = {}
    try:
        with open(path) as f:
            recs = json.load(f)
    except (OSError, ValueError):
        pass
    import time as _time

    recs[name] = {"step_ms": float(step_ms),
                  "calib_hash": _calib_hash(),
                  "updated": _time.time()}
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(recs, f)
        os.replace(tmp, path)
    except OSError:
        pass                        # measurements are advisory
    return recs[name]


def measured_steps(n_devices, platform=None, cache_dir=None) -> dict:
    """{layout_name: step_ms} of code-current measured records for this
    (platform, device count) — entries from a different calib-hash
    epoch are dropped (the estimates they would re-rank against were
    produced by different model code)."""
    import jax

    if platform is None:
        devs = jax.devices()
        platform = devs[0].platform if devs else "none"
    path = _measured_path(platform, int(n_devices), cache_dir)
    try:
        with open(path) as f:
            recs = json.load(f)
    except (OSError, ValueError):
        return {}
    want = _calib_hash()
    return {k: float(v["step_ms"]) for k, v in recs.items()
            if isinstance(v, dict) and v.get("calib_hash") == want}


def spec_of_model(config, global_batch, seq_len=None, params=None):
    """Build a `ModelSpec` from a GPTConfig-shaped config object."""
    h = int(config.hidden_size)
    L = int(config.num_layers)
    V = int(config.vocab_size)
    inter = int(getattr(config, "intermediate_size", 4 * h) or 4 * h)
    experts = int(getattr(config, "num_experts", 0) or 0)
    ffn = 2 * h * inter
    if params is None:
        # transformer param count: embeddings + per-layer qkv/proj/mlp/ln
        # (MoE: num_experts expert FFNs replace the single dense one)
        per_layer_ffn = ffn * max(experts, 1)
        params = (V * h + int(config.max_position_embeddings) * h
                  + L * (4 * h * h + per_layer_ffn + 9 * h) + 2 * h)
    expert_frac = 0.0
    if experts:
        expert_frac = (L * ffn * experts) / max(int(params), 1)
    return ModelSpec(
        params=int(params), num_layers=L, hidden_size=h,
        num_heads=int(config.num_attention_heads), vocab_size=V,
        seq_len=int(seq_len or config.max_position_embeddings),
        global_batch=int(global_batch),
        use_recompute=bool(getattr(config, "use_recompute", False)),
        num_experts=experts, expert_param_frac=expert_frac,
        # the steps select_train_step builds default to sharded param
        # storage (ISSUE 11) — the cost/memory model should rank what
        # will actually run
        sharded_param_storage=True,
    )


def _parse_env_layout(text):
    out = {}
    for part in text.replace(";", ",").split(","):
        part = part.strip()
        if not part:
            continue
        k, _, v = part.partition("=")
        k = k.strip().lower()
        if k not in ("dp", "mp", "pp", "ep", "micro"):
            raise ValueError(
                f"{LAYOUT_ENV}: unknown key {k!r} (dp/mp/pp/ep/micro; "
                "weight-update sharding always rides the dp axis — "
                "there is no separate sharding degree to force)")
        out[k] = int(v)
    return out


# scan_unroll / layer_chunk for the picked layout. No record of a sweep
# is read back: a number taken on another machine or an older program
# never steers the step's shape.
_SCAN_KNOBS = {"scan_unroll": 2, "layer_chunk": 1, "source": "default"}


def _rank_corr(xs, ys):
    """Spearman rank correlation of two equal-length sequences (n >= 2);
    ties broken by position — enough for the small top-k tables here."""
    def ranks(vals):
        order = sorted(range(len(vals)), key=lambda i: vals[i])
        r = [0] * len(vals)
        for rank, i in enumerate(order):
            r[i] = rank
        return r

    rx, ry = ranks(xs), ranks(ys)
    n = len(xs)
    d2 = sum((a - b) ** 2 for a, b in zip(rx, ry))
    return 1.0 - 6.0 * d2 / (n * (n * n - 1))


def pick_layout(spec, n_devices, hbm_gb=16.0, backend=None,
                max_micro=32, env=None, top_k=5, measured=None):
    """Choose a runnable hybrid layout for `spec` on `n_devices` chips.

    Returns a dict: ``candidate`` (the winning `Candidate`),
    ``mesh_degrees`` ({axis: degree} for `env.build_mesh`),
    ``scan_unroll``/``layer_chunk``/``comm_bucket_mb``, ``source``
    ("planner" or "env"), and ``ranking`` (the top-k (name, est_ms)
    table the decision came from). Raises if nothing feasible survives
    pruning (including a forced env layout that fails the rules).

    Measured re-ranking (ISSUE 17): where `record_measured_step` has a
    code-current timeline for a candidate, the MEASURED step time
    replaces the calibrated estimate in the sort — live data beats the
    cost model. ``measured`` overrides the on-disk store ({name:
    step_ms}; pass ``{}`` to disable). When >= 2 candidates have both
    numbers, the decision carries ``rho_divergence`` (1 - Spearman of
    estimated-vs-measured order) and flags divergence > 0.5 to the
    flight recorder — the signal that the §14 calibration has drifted
    from reality and needs a re-run.
    """
    env_map = os.environ if env is None else env
    forced = env_map.get(LAYOUT_ENV, "").strip()
    from ...utils import flags as _flags

    bucket_mb = int(_flags.get_flag("FLAGS_comm_bucket_mb") or 25)
    knobs = _SCAN_KNOBS

    def finish(cand, source, ranking):
        return {
            "candidate": cand,
            "mesh_degrees": {k: v for k, v in
                             (("dp", cand.dp), ("pp", cand.pp),
                              ("mp", cand.mp), ("ep", cand.ep))
                             if v > 1 or k == "dp"},
            "num_micro": int(cand.micro_batch),
            "scan_unroll": knobs["scan_unroll"],
            "layer_chunk": knobs["layer_chunk"],
            "knob_source": knobs["source"],
            "comm_bucket_mb": bucket_mb,
            "source": source,
            "ranking": ranking,
        }

    if forced:
        kv = _parse_env_layout(forced)
        dp = kv.get("dp", 0) or max(
            1, n_devices // (kv.get("mp", 1) * kv.get("pp", 1)
                             * kv.get("ep", 1)))
        cand = Candidate(dp=dp, mp=kv.get("mp", 1), pp=kv.get("pp", 1),
                         ep=kv.get("ep", 1),
                         sharding_stage=1,
                         micro_batch=kv.get("micro",
                                            2 if kv.get("pp", 1) > 1
                                            else 1))
        if cand.degree > n_devices:
            raise ValueError(
                f"{LAYOUT_ENV}={forced!r} needs {cand.degree} devices, "
                f"have {n_devices}")
        pruned = prune_candidates([cand], spec, hbm_gb)[0]
        if pruned.pruned_reason:
            raise ValueError(
                f"{LAYOUT_ENV}={forced!r} is infeasible: "
                f"{pruned.pruned_reason}")
        return finish(cand, "env", [])

    cands = grid_candidates(n_devices, sharding_stages=(1,),
                            max_micro=max_micro,
                            global_batch=spec.global_batch,
                            num_experts=getattr(spec, "num_experts", 0))
    # restrict to what the hybrid steps actually run today: no sep ring
    # here (dp×mp, dp×pp, dp×ep and the full dp×mp×pp composition all
    # run; mp×ep / pp×ep fall out of the pruning rules);
    # C % pp falls out of the num_layers % pp pruning rule
    cands = [c for c in cands
             if c.sep == 1 and c.degree == n_devices]
    cands = prune_candidates(cands, spec, hbm_gb)
    live = [c for c in cands if c.pruned_reason is None]
    if not live:
        reasons = sorted({c.pruned_reason for c in cands
                          if c.pruned_reason})
        raise ValueError(
            f"no feasible hybrid layout for {n_devices} devices "
            f"(pruned: {reasons[:6]})")
    for c in live:
        c.estimated_mem_gb = estimate_memory_gb(spec, c)
        c.estimated_step_ms = estimate_step_ms(spec, c, backend=backend)
    if measured is None:
        measured = measured_steps(n_devices)
    meas = {layout_name(c): measured[layout_name(c)]
            for c in live if layout_name(c) in measured}

    def effective_ms(c):
        return meas.get(layout_name(c), c.estimated_step_ms)

    live.sort(key=lambda c: (effective_ms(c),
                             c.mp + c.pp))  # tie-break: simpler layout
    ranking = [(layout_name(c), round(effective_ms(c), 3))
               for c in live[:top_k]]
    dec = finish(live[0], "planner", ranking)
    dec["measured"] = dict(meas)
    rho_div = 0.0
    if len(meas) >= 2:
        both = [c for c in live if layout_name(c) in meas]
        rho = _rank_corr([c.estimated_step_ms for c in both],
                         [meas[layout_name(c)] for c in both])
        rho_div = max(0.0, 1.0 - rho)
    dec["rho_divergence"] = round(rho_div, 4)
    try:
        from ...observability import recorder, registry

        registry().gauge("planner.rho_divergence").set(rho_div)
        if rho_div > 0.5:
            recorder().note(
                "planner_rho_divergence", divergence=round(rho_div, 4),
                measured=len(meas), winner=ranking[0][0] if ranking
                else None)
    except Exception:
        pass                 # observability must never break selection
    return dec
