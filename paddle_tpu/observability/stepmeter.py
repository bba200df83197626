"""Per-step MFU / FLOPs accounting (``train.mfu`` and friends).

Combines two measurements:

* **FLOPs per step** from XLA cost analysis — the same
  ``jit(...).lower(...).compile().cost_analysis()`` API ``paddle.flops``
  uses, divided by 2 to match the MAC-as-one-FLOP convention shared by
  ``paddle.flops`` and bench.py's analytic constants (ResNet-50 fwd @224
  = 4.09 GFLOPs/img under that convention; XLA reports ~8.2e9 raw).
* **Step wall time** measured by the caller around a *synchronizing* step
  (the hapi train loop's loss fetch forces the sync, so wall time there is
  real device+host time, not async-dispatch time — the LazyTensor
  distinction PAPERS.md stresses).

``StepMeter.step(wall_s)`` publishes ``<prefix>.mfu``,
``<prefix>.flops_per_step`` and a ``<prefix>.step_ms`` histogram to a
StatRegistry, replacing hand-computed bench numbers with live stats.
"""
from __future__ import annotations

from typing import Optional

from ..core import monitor as _monitor


#: Peak dense bf16 FLOP/s per chip, keyed by ``jax.Device.device_kind``.
#: The one peaks table of the repo (bench.py reads it too). Sources:
#: "TPU v5 lite" is how JAX names a TPU v5e chip — 197 TFLOP/s bf16,
#: Google Cloud documentation, "TPU v5e".
PEAK_FLOPS_BY_DEVICE_KIND = {
    "TPU v5 lite": 197.0e12,
}


def default_peak_flops() -> Optional[float]:
    """Peak FLOP/s of the local accelerator from
    :data:`PEAK_FLOPS_BY_DEVICE_KIND`. The CPU has no peak (None — no MFU
    is published for it); an accelerator that is not in the table is an
    error, never a default."""
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    peak = PEAK_FLOPS_BY_DEVICE_KIND.get(dev.device_kind)
    if peak is None:
        raise KeyError(
            f"no peak FLOP/s listed for device_kind {dev.device_kind!r} "
            f"(platform {dev.platform!r}); add it with its source to "
            f"PEAK_FLOPS_BY_DEVICE_KIND in "
            f"paddle_tpu/observability/stepmeter.py")
    return peak


def compiled_flops(fn, *args, jit_kwargs: Optional[dict] = None,
                   mac_convention: bool = True, **kwargs) -> Optional[float]:
    """FLOPs of one execution of ``fn(*args, **kwargs)`` per XLA cost
    analysis (compiles without executing). Returns None when the backend
    reports no cost model. ``mac_convention`` halves XLA's raw count to
    match ``paddle.flops`` / bench.py accounting."""
    import jax
    try:
        compiled = jax.jit(fn, **(jit_kwargs or {})).lower(
            *args, **kwargs).compile()
        flops = float(compiled.cost_analysis().get("flops", 0.0))
    except Exception:
        return None
    if flops <= 0.0:
        return None
    return flops / 2.0 if mac_convention else flops


class StepMeter:
    """Publishes live MFU from (flops per step, measured wall per step).

    ``flops_per_step`` is set once per compiled signature (cost analysis
    is a compile, not a per-step cost); ``step()`` is the per-step hot
    call — two registry writes and one histogram observe."""

    def __init__(self, peak_flops: Optional[float] = None,
                 registry: Optional["_monitor.StatRegistry"] = None,
                 prefix: str = "train"):
        # None on the CPU: step() then records wall time and FLOPs only
        self.peak_flops = (float(peak_flops) if peak_flops
                           else default_peak_flops())
        self.registry = (registry if registry is not None
                         else _monitor.default_registry())
        self.prefix = prefix
        self.flops_per_step: Optional[float] = None
        self.last_mfu: Optional[float] = None

    def set_flops_per_step(self, flops: Optional[float]):
        if flops:
            self.flops_per_step = float(flops)
            self.registry.set(f"{self.prefix}.flops_per_step",
                              self.flops_per_step)

    def measure_flops(self, fn, *args, jit_kwargs: Optional[dict] = None,
                      **kwargs) -> Optional[float]:
        """Cost-analyze ``fn`` and adopt the result as flops_per_step."""
        self.set_flops_per_step(compiled_flops(
            fn, *args, jit_kwargs=jit_kwargs, **kwargs))
        return self.flops_per_step

    def step(self, wall_s: float, flops: Optional[float] = None
             ) -> Optional[float]:
        """Record one step; returns the step's MFU (None if flops, wall
        or the device's peak are unknown). ``flops`` overrides the sticky
        per-signature value (e.g. a step that ran a different compiled
        program)."""
        reg = self.registry
        p = self.prefix
        reg.observe(f"{p}.step_ms", wall_s * 1e3)
        f = flops if flops is not None else self.flops_per_step
        if not f or wall_s <= 0.0 or self.peak_flops is None:
            return None
        mfu = f / wall_s / self.peak_flops
        self.last_mfu = mfu
        reg.set(f"{p}.mfu", mfu)
        reg.observe(f"{p}.mfu_pct", mfu * 100.0)
        return mfu
