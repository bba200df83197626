"""What a token and layer hold in the cache, of what its heads' keys and
values would take (``kv_row_bytes / kv_row_bytes_expanded``, the engine's
``stats()["latent_cache_row_share"]``): 0.125 for 640 floats of 5,120; 1.0
would mean the cache holds expanded rows."""


def read(run):
    return (run.get("gauges") or {}).get("latent_cache_row_share")
