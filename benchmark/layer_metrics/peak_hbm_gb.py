"""The program's sampled peak of device memory (GB). Serves
``peak_hbm_gb.train``, ``.closed`` and ``.open``."""


def read(run):
    return run["peak_bytes"] / 1e9 if run.get("peak_bytes") else None
