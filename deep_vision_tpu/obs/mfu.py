"""Serving MFU: analytic FLOPs over measured compute-stage seconds.

Training's MFU is the benchmark's ``step_mfu_pct`` (``benchmark/``, read
from a device trace); this meter is serving's.  Each bucket program's FLOP count comes from XLA's own cost analysis on the AOT
executable (``jax.jit(...).lower(...).compile().cost_analysis()`` —
the registry attaches it to the bucket callable at compile time), and
the engine feeds in the measured per-batch compute-stage seconds it
already derives for admission control (completion minus the later of
dispatch or the previous batch's completion, i.e. device occupancy
under pipelining, not queue wait).

    serving_mfu = Σ(batches_b × flops_b) / Σ compute_s / peak_flops

Fallback, documented: when XLA cost analysis is unavailable (a loaded
StableHLO blob has no compiled object; some backends return no
``flops`` key) the registry substitutes ``2 × params × batch`` — a
dense-matmul LOWER BOUND that ignores convolution reuse — and labels
the source ``params_lower_bound`` so a too-good-to-be-true gauge is
never silently wrong.  Peak FLOP/s comes from the public spec-sheet
table below (bf16 dense, per chip).  A
device that is not in the table has no peak: ``peak_tflops`` raises and
the meter reports ``serving_mfu: None`` — a CPU run counts FLOPs and
compute seconds but never divides them by some other chip's rate.
"""

from __future__ import annotations

import threading

from deep_vision_tpu.analysis.sanitizer import new_lock

# peak dense bf16 TFLOP/s per chip by device kind (public spec sheets)
PEAK_BF16_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,   # v5e
    "TPU v5": 459.0,        # v5p
    "TPU v6 lite": 918.0,   # Trillium
}


def peak_tflops(device_kind: str | None = None) -> float:
    """Peak bf16 TFLOP/s for a device kind (current backend if None);
    ``LookupError`` for a device the table does not list."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    for k, v in PEAK_BF16_TFLOPS.items():
        if device_kind.startswith(k):
            return v
    raise LookupError(
        f"no peak bf16 TFLOP/s on record for device kind "
        f"'{device_kind}' (have {sorted(PEAK_BF16_TFLOPS)}); an MFU "
        f"needs the real chip's peak — add it to PEAK_BF16_TFLOPS with "
        f"its source")


def peak_flops_per_s(device_kind: str | None = None) -> float:
    return peak_tflops(device_kind) * 1e12


def compiled_flops(compiled) -> float | None:
    """FLOPs of one executable per XLA's cost analysis (honest MFU
    numerator — no hand-derived constants); None when the backend
    doesn't report it.  On a GSPMD-sharded executable the analysis
    covers ONE partition's program — per-shard FLOPs — which is exactly
    the per-chip numerator the meter wants against its per-chip peak
    (a 2×2 mesh running 4 shards shows the same MFU each chip does)."""
    try:
        cost = compiled.cost_analysis()
        ca = cost[0] if isinstance(cost, (list, tuple)) else cost
        return float(ca.get("flops", 0.0)) or None
    except Exception:  # noqa: BLE001 — cost analysis is best-effort
        return None


def params_flops_lower_bound(variables, batch: int,
                             devices: int = 1) -> float:
    """The documented fallback: 2 × param count × batch (one
    multiply-add per weight per image — exact for dense layers, a lower
    bound for convolutions, which reuse each weight spatially).

    Counts float leaves AND int8 leaves: a quantized variables tree
    (serve/quant.py) stores its conv/dense kernels as int8, but each
    dequantized weight still does one MAC per image — excluding them
    would collapse the int8 serving-MFU numerator to biases+scales.

    ``devices`` keeps the per-chip semantics on mesh views: the global
    2·params·batch work divides across the mesh, matching what
    ``compiled_flops`` reports for one partition of a sharded
    executable (the meter's peak is per chip)."""
    import jax
    import numpy as np

    i8 = np.dtype("int8")

    def _counts(a) -> bool:
        dt = getattr(a, "dtype", np.dtype("O"))
        return dt.kind == "f" or dt == i8

    n = sum(int(np.prod(a.shape))
            for a in jax.tree_util.tree_leaves(variables) if _counts(a))
    return 2.0 * n * batch / max(1, int(devices))


def round_mfu(mfu: float | None) -> float | None:
    """6 SIGNIFICANT digits, not 6 decimals: a CPU smoke run's honest
    ~1e-8 MFU must survive reporting instead of rounding to 0."""
    return float(f"{mfu:.6g}") if mfu is not None else None


class MfuMeter:
    """Accumulates (bucket flops × batches) and compute seconds.

    Thread-safe under its own lock: ``observe`` is called from the
    drainer (pipelined path) and from the synchronous retry path.  The
    peak resolves lazily on first ``report`` so constructing an engine
    never initializes the JAX backend; on a device without a known peak
    it stays None and so does the MFU.
    """

    def __init__(self, peak: float | None = None):
        self._lock = new_lock("obs.mfu.MfuMeter._lock")
        self._peak = peak
        self._peak_resolved = peak is not None
        self._bucket_flops: dict[int, float | None] = {}  # guarded-by: _lock
        self._source: str | None = None  # guarded-by: _lock
        self.batches = 0  # guarded-by: _lock
        self.images = 0  # guarded-by: _lock
        self.compute_s = 0.0  # guarded-by: _lock
        self.flops = 0.0  # guarded-by: _lock
        self.unknown_flops_batches = 0  # guarded-by: _lock

    def set_bucket_flops(self, bucket: int, flops: float | None,
                         source: str | None = None):
        with self._lock:
            self._bucket_flops[int(bucket)] = flops
            if source is not None:
                self._source = source

    def observe(self, bucket: int, images: int, compute_s: float):
        """One executed batch: its bucket, live image count, and
        measured compute-stage seconds."""
        with self._lock:
            self.batches += 1
            self.images += int(images)
            self.compute_s += max(0.0, float(compute_s))
            f = self._bucket_flops.get(int(bucket))
            if f:
                self.flops += f
            else:
                self.unknown_flops_batches += 1

    def peak(self) -> float | None:
        if not self._peak_resolved:
            try:
                self._peak = peak_flops_per_s()
            except LookupError:
                self._peak = None  # unknown device: no peak, no MFU
            self._peak_resolved = True
        return self._peak

    def mfu(self) -> float | None:
        with self._lock:
            if self.compute_s <= 0 or self.flops <= 0:
                return None
            flops, secs = self.flops, self.compute_s
        peak = self.peak()
        return flops / secs / peak if peak else None

    def report(self) -> dict:
        mfu = self.mfu()
        with self._lock:
            return {"serving_mfu": round_mfu(mfu),
                    "flops_total": self.flops,
                    "compute_s": round(self.compute_s, 6),
                    "batches": self.batches,
                    "images": self.images,
                    "unknown_flops_batches": self.unknown_flops_batches,
                    "peak_flops_per_s": self._peak,
                    "flops_source": self._source,
                    "flops_by_bucket": {
                        str(b): f for b, f in
                        sorted(self._bucket_flops.items())}}

    @staticmethod
    def merged_report(meters: list["MfuMeter"]) -> dict:
        """Fleet view over replica meters (same process, same peak):
        FLOPs and compute seconds sum; MFU recomputes from the sums."""
        flops = sum(m.flops for m in meters)
        secs = sum(m.compute_s for m in meters)
        peak = meters[0].peak() if meters else None
        mfu = flops / secs / peak \
            if peak and secs > 0 and flops > 0 else None
        by_bucket: dict[str, float | None] = {}
        for m in meters:
            for b, f in m._bucket_flops.items():
                by_bucket.setdefault(str(b), f)
        return {"serving_mfu": round_mfu(mfu),
                "flops_total": flops,
                "compute_s": round(secs, 6),
                "batches": sum(m.batches for m in meters),
                "images": sum(m.images for m in meters),
                "unknown_flops_batches": sum(m.unknown_flops_batches
                                             for m in meters),
                "peak_flops_per_s": peak,
                "flops_source": next((m._source for m in meters
                                      if m._source), None),
                "flops_by_bucket": dict(sorted(by_bucket.items()))}
