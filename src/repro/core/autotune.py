"""C4: block-multiplier ("LMUL") selection for Pallas kernels.

RVV's LMUL trades elements-per-instruction against register pressure; the
TPU analogue trades VMEM tile size against:
  * grid overhead + pipeline ramp (favors LARGE tiles),
  * VMEM capacity: when the per-step working set exceeds the VMEM budget
    the pipeline loses double-buffering and ultimately spills — the cliff
    the paper sees at LMUL=8 (Fig 7).

``select_multiplier`` is a pure cost-model decision (no hardware needed):
for each multiplier it computes the working set from the kernel's block
shapes and predicts the bound term; ``measured_sweep`` is the validation
half — it times real candidate callables through ``repro.perf.measure``
(interleaved repeats, medians) so benchmarks/fig7 can check that
"default ≈ optimal" transfers to this host.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import pathlib
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.core.costmodel import TPU_V5E, HWSpec
from repro.kernels.common import (MXU, SCOPED_VMEM_BYTES, SUBLANE,
                                  VALID_MULTIPLIERS)
from repro.perf.measure import measure_group

# On-disk best-config cache: a schema-valid perf Report (rows =
# {key, best, medians_s, reps}) so `python -m repro.perf --validate`
# accepts it alongside every other benchmarks/results artifact and the
# ci.sh legacy-pruner keeps it.  Repeated serve runs skip the sweep;
# retune=True forces re-measurement (serve_bench exposes --retune).
AUTOTUNE_CACHE_PATH = (pathlib.Path(__file__).resolve().parents[3]
                       / "benchmarks" / "results" / "autotune_cache.json")


@dataclasses.dataclass
class KernelShape:
    """Per-grid-step footprint of a kernel at multiplier 1."""
    name: str
    base_block_bytes: float        # VMEM bytes of all blocks at m=1
    block_scaling: float           # exponent: bytes ~ m**scaling (1 or 2)
    flops_per_step: float          # at m=1
    hbm_bytes_per_step: float      # at m=1
    grid_steps: int                # at m=1


@dataclasses.dataclass
class TuneReport:
    multiplier: int
    working_set: float
    predicted_s: float
    bound: str
    fits_vmem: bool


GRID_STEP_OVERHEAD_S = 1.5e-6      # DMA issue + scalar-core loop bookkeeping


def predict(ks: KernelShape, m: int, hw: HWSpec = TPU_V5E) -> TuneReport:
    ws = ks.base_block_bytes * (m ** ks.block_scaling)
    steps = max(1, ks.grid_steps // (m ** ks.block_scaling))
    flops = ks.flops_per_step * (m ** ks.block_scaling)
    bytes_ = ks.hbm_bytes_per_step * (m ** ks.block_scaling)
    t_compute = flops / hw.peak_flops_bf16
    t_mem = bytes_ / hw.hbm_bw
    # VMEM penalty: need 2x (double buffering); >budget means serialization
    fits = 2 * ws <= hw.vmem_bytes
    penalty = 1.0 if fits else (2 * ws / hw.vmem_bytes)
    t_step = max(t_compute, t_mem) * penalty + GRID_STEP_OVERHEAD_S
    bound = "compute" if t_compute >= t_mem else "memory"
    if not fits:
        bound = "vmem-spill"
    return TuneReport(m, ws, t_step * steps, bound, fits)


def select_multiplier(ks: KernelShape,
                      hw: HWSpec = TPU_V5E) -> Tuple[int, List[TuneReport]]:
    reports = [predict(ks, m, hw) for m in VALID_MULTIPLIERS]
    best = min(reports, key=lambda r: r.predicted_s)
    return best.multiplier, reports


def measured_sweep(candidates: Dict[str, Tuple[Callable, tuple]],
                   reps: int = 3) -> Dict[str, float]:
    """Host-measured validation sweep over block-knob candidates.

    ``candidates`` maps a label (e.g. a kv-chunk size) to ``(fn, args)``;
    all candidates are timed in the same interleaved rounds and the
    returned dict carries each label's median wall seconds.
    """
    return {name: m.median_s
            for name, m in measure_group(candidates, reps=reps).items()}


# -- persistent best-config cache -------------------------------------------
def _load_cache_rows(path: pathlib.Path) -> List[Dict[str, Any]]:
    try:
        payload = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError):
        return []
    if not isinstance(payload, dict) \
            or payload.get("schema") != "repro.perf.report":
        return []
    rows = payload.get("rows")
    return rows if isinstance(rows, list) else []


def _write_cache_rows(path: pathlib.Path,
                      rows: List[Dict[str, Any]]) -> None:
    from repro.perf import report as perf_report
    rep = perf_report.make_report(
        "autotune_cache", rows,
        meta={"writer": "repro.core.autotune.cached_best_config",
              "statistic": "median_s (interleaved measured_sweep)"})
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(rep.to_json())


def cached_best_config(key: str,
                       candidates: Dict[str, Tuple[Callable, tuple]], *,
                       reps: int = 3, retune: bool = False,
                       cache_path: Optional[pathlib.Path] = None
                       ) -> Dict[str, Any]:
    """``measured_sweep`` with an on-disk memo.

    A cache row matches when its ``key`` AND candidate-label set agree
    (a changed candidate grid invalidates the row).  Returns
    ``{key, best, medians_s, reps, source}`` with ``source`` one of
    ``"cache"`` / ``"measured"``.
    """
    path = pathlib.Path(cache_path) if cache_path else AUTOTUNE_CACHE_PATH
    rows = _load_cache_rows(path)
    labels = sorted(candidates)
    if not retune:
        for row in rows:
            if (row.get("key") == key
                    and sorted(row.get("medians_s", {})) == labels):
                return {**row, "source": "cache"}
    medians = measured_sweep(candidates, reps=reps)
    row = {"key": key, "best": min(medians, key=medians.get),
           "medians_s": {k: float(v) for k, v in medians.items()},
           "reps": reps}
    _write_cache_rows(path,
                      [r for r in rows if r.get("key") != key] + [row])
    return {**row, "source": "measured"}


def paged_block_candidates(*, pps: int, page_size: int, n_kv_heads: int,
                           head_dim: int, rows: int,
                           dtype_bytes: int) -> List[int]:
    """The ``block_pages`` tiles ``tune_paged_attention`` may pick: 1, 2,
    4, 8 and the whole row (``pps``), where they divide ``pps`` and the
    kernel's VMEM footprint at ``rows`` query rows per KV head fits
    ``SCOPED_VMEM_BYTES``."""
    from repro.kernels.paged_attention import kernel as pa_kernel

    bps = sorted({bp for bp in (1, 2, 4, 8, pps)
                  if 1 <= bp <= pps and pps % bp == 0})
    footprint = {bp: pa_kernel.vmem_bytes(
        block_pages=bp, page_size=page_size, n_kv_heads=n_kv_heads,
        head_dim=head_dim, rows=rows, dtype_bytes=dtype_bytes)
        for bp in bps}
    bps = [bp for bp in bps if footprint[bp] <= SCOPED_VMEM_BYTES]
    if not bps:
        raise ValueError(
            f"no block_pages candidate fits {SCOPED_VMEM_BYTES} B of VMEM: "
            f"footprints {footprint}")
    return bps


def tune_paged_attention(*, n_slots: int, max_len: int, page_size: int,
                         n_kv_heads: int, n_q_heads: int, head_dim: int,
                         dtype: str, impl: Optional[str] = None,
                         max_q_len: int = 1,
                         reps: int = 3, retune: bool = False,
                         cache_path: Optional[pathlib.Path] = None
                         ) -> Dict[str, Any]:
    """Sweep ``block_pages`` (pages streamed per tile) for the paged
    flash-decode kernel at the engine's decode shapes.

    Keyed on (head_dim, n_kv_heads, page_size, dtype) plus
    pages_per_seq, the impl and the device kind — engines with
    different cache lengths have different candidate grids, and a row
    measured on one device never steers another.  Only candidates whose
    kernel VMEM footprint (``kernel.vmem_bytes`` at the widest query
    block the engine runs, ``max_q_len`` columns) fits Mosaic's scoped
    VMEM limit (``SCOPED_VMEM_BYTES``) are swept.  Candidates are
    full-cache decode calls timed as interleaved contenders
    (``measured_sweep``); impl/backend resolution matches what the
    engine will actually run.
    """
    import jax
    import jax.numpy as jnp
    from repro.kernels.paged_attention import ops as pa_ops

    pps = max_len // page_size
    group = max(n_q_heads // n_kv_heads, 1)
    impl = pa_ops.resolve_impl(impl)
    key = (f"paged_attention/hd{head_dim}/nkv{n_kv_heads}"
           f"/g{group}/page{page_size}/pps{pps}/{dtype}/{impl}"
           f"/{jax.devices()[0].device_kind}")
    jdt = jnp.dtype(dtype)
    k0 = jax.random.key(0)
    q = jax.random.normal(
        k0, (n_slots, 1, n_q_heads, head_dim), jnp.float32).astype(jdt)
    kp = jax.random.normal(
        jax.random.fold_in(k0, 1),
        (n_slots * pps, page_size, n_kv_heads, head_dim),
        jnp.float32).astype(jdt)
    vp = jax.random.normal(
        jax.random.fold_in(k0, 2), kp.shape, jnp.float32).astype(jdt)
    idx = jnp.arange(n_slots * pps, dtype=jnp.int32).reshape(n_slots, pps)
    positions = jnp.full((n_slots, 1), max_len - 1, jnp.int32)
    valid = jnp.full((n_slots,), max_len, jnp.int32)
    bps = paged_block_candidates(
        pps=pps, page_size=page_size, n_kv_heads=n_kv_heads,
        head_dim=head_dim, rows=group * max_q_len, dtype_bytes=jdt.itemsize)
    candidates = {
        f"bp{bp}": (functools.partial(
            pa_ops.paged_attention, page_size=page_size, block_pages=bp,
            impl=impl), (q, kp, vp, idx, positions, valid))
        for bp in bps}
    res = cached_best_config(key, candidates, reps=reps, retune=retune,
                             cache_path=cache_path)
    return {"key": res["key"], "impl": impl, "best": res["best"],
            "block_pages": int(res["best"][2:]),
            "medians_s": res["medians_s"], "source": res["source"]}


# -- footprint builders for the shipped kernels -----------------------------
def gemm_shape(M: int, K: int, N: int, bk: int = 512,
               dtype_bytes: int = 2) -> KernelShape:
    bm = bn = MXU
    bk = min(bk, K)
    base = (bm * bk + bk * bn) * dtype_bytes + bm * bn * 4  # A + B + acc
    steps = (M // bm) * (N // bn) * (K // bk)
    return KernelShape(
        name="gemm", base_block_bytes=base, block_scaling=2,
        flops_per_step=2.0 * bm * bn * bk,
        hbm_bytes_per_step=(bm * bk + bk * bn) * dtype_bytes,
        grid_steps=steps)


def stream_shape(n_elems: int, dtype_bytes: int = 4,
                 n_arrays: int = 3) -> KernelShape:
    br = SUBLANE
    base = n_arrays * br * 128 * dtype_bytes
    return KernelShape(
        name="stream", base_block_bytes=base, block_scaling=1,
        flops_per_step=br * 128 * 2,
        hbm_bytes_per_step=base,
        grid_steps=n_elems // (br * 128))


def flash_shape(S: int, H: int, dtype_bytes: int = 2,
                block: int = 512) -> KernelShape:
    base = (block * H * 3) * dtype_bytes + block * block * 4 + block * H * 4
    steps = (S // block) ** 2 // 2
    return KernelShape(
        name="flash_attention", base_block_bytes=base, block_scaling=2,
        flops_per_step=4.0 * block * block * H,
        hbm_bytes_per_step=2 * block * H * dtype_bytes,
        grid_steps=max(steps, 1))
