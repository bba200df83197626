"""Device time of the expert product over the HELD experts (kernels
``moe_experts_up`` and ``moe_experts_down`` at the decode tick's rows, every
expert layer) in one decode tick (ms)."""
from benchmark import costs_lfm2


def read(run):
    if "share" not in ((run.get("cell") or {}).get("config_data") or {}):
        return None
    m = costs_lfm2.decode_moe(run)
    return None if m is None else m[0] / m[1] * 1e3
