"""What the linear layers keep beside the pages (GB): a ``[32, 128, 128]``
float32 state and three rows of the convolution's inputs a slot and layer,
the engine's gauge ``gdn_state_bytes``. It does not grow with a context."""


def read(run):
    held = (run.get("gauges") or {}).get("gdn_state_bytes")
    return held / 1e9 if held else None
