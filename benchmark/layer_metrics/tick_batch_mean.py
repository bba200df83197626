"""Mean sequences per decode tick, from the engine's counters: tokens
generated less the one each prefill emits, over the ticks. Serves
``tick_batch_mean.closed``."""


def read(run):
    ticks = run.get("hist", {}).get("decode_tick_ms", {}).get("count", 0)
    c = run.get("counters", {})
    if not ticks:
        return None
    return (c.get("tokens_generated", 0) - c.get("prefills", 0)) / ticks
