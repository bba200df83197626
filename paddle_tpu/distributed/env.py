"""Process/device environment for distributed training.

TPU-native replacement for the reference's env-var handshake + NCCL bootstrap
(reference: python/paddle/distributed/parallel.py:60 init_parallel_env →
imperative/nccl_context.cc:53 NCCLParallelContext::Init — TCP-broadcast of
ncclUniqueId + ncclCommInitRank; platform/gen_comm_id_helper.cc).

On TPU the transport is XLA's ICI/DCN: `jax.distributed.initialize`
(coordinator address ≈ PADDLE_TRAINER_ENDPOINTS[0]) wires every host into one
global runtime; there are no ring ids or comm streams to manage. The
reference's env contract (PADDLE_TRAINER_ID, PADDLE_TRAINERS_NUM,
PADDLE_TRAINER_ENDPOINTS) is honored so launcher scripts port unchanged.
"""
from __future__ import annotations

import logging
import os
from typing import Optional

import jax

_LOG = logging.getLogger(__name__)

# paddle_tpu/__init__ performs the pre-backend bootstrap (by calling
# bootstrap_pre_backend below on a standalone load of this module) and
# leaves this sentinel; pick it up so init_parallel_env is a no-op after it
_INITIALIZED = [bool(os.environ.get("_PADDLE_TPU_DIST_INITIALIZED"))]


class ParallelEnv:
    """reference: fluid/dygraph/parallel.py:70 ParallelEnv."""

    def __init__(self):
        self._rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
        self._world_size = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
        self._endpoints = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "").split(",")
        self._current_endpoint = os.environ.get("PADDLE_CURRENT_ENDPOINT", "")
        self._device_id = int(os.environ.get("FLAGS_selected_devices",
                                             os.environ.get("FLAGS_selected_gpus", "0"))
                              .split(",")[0] or 0)

    @property
    def rank(self):
        if _INITIALIZED[0]:
            return jax.process_index()
        return self._rank

    local_rank = rank

    @property
    def world_size(self):
        if _INITIALIZED[0]:
            return jax.process_count()
        return self._world_size

    nranks = world_size

    @property
    def device_id(self):
        return self._device_id

    @property
    def dev_id(self):
        return self._device_id

    @property
    def current_endpoint(self):
        return self._current_endpoint

    @property
    def trainer_endpoints(self):
        return self._endpoints


def _resilience():
    """``paddle_tpu.utils.resilience`` WITHOUT importing the
    ``paddle_tpu.utils`` package — its ``__init__`` pulls vision/nn, which
    run backend-touching computations at import, and this module's callers
    include the pre-backend bootstrap where the backend must not exist yet.
    resilience.py itself is stdlib-only, so load it standalone under its
    canonical dotted name; the later package import finds this sys.modules
    entry and reuses it (one module object, one FaultInjector singleton)."""
    import sys
    name = "paddle_tpu.utils.resilience"
    mod = sys.modules.get(name)
    if mod is None:
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "utils", "resilience.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return mod


def _initialize_distributed_with_retry(coordinator, num_processes,
                                       process_id):
    """``jax.distributed.initialize`` with backoff — workers racing the
    coordinator at job start must wait for it, not fail fast. Total budget
    from PADDLE_TPU_INIT_TIMEOUT (seconds, default 300); each retry logs
    the attempt count and coordinator address so a wedged bootstrap is
    diagnosable from the worker log alone."""
    res = _resilience()
    Deadline, RetryError, retry_call = (res.Deadline, res.RetryError,
                                        res.retry_call)

    deadline = Deadline.from_env("PADDLE_TPU_INIT_TIMEOUT", 300.0)

    def _attempt():
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id)

    def _log_retry(attempt, exc, pause):
        _LOG.warning(
            "jax.distributed.initialize attempt %d against coordinator %s "
            "failed (%s); retrying in %.1fs "
            "(budget PADDLE_TPU_INIT_TIMEOUT=%ss)",
            attempt, coordinator, exc, pause, deadline.seconds)

    try:
        retry_call(_attempt, max_attempts=1000, backoff=1.0, max_backoff=15.0,
                   deadline=deadline, on_retry=_log_retry)
    except RetryError as e:
        raise RuntimeError(
            f"jax.distributed.initialize(coordinator={coordinator}, "
            f"num_processes={num_processes}, process_id={process_id}) did "
            f"not come up within PADDLE_TPU_INIT_TIMEOUT="
            f"{deadline.seconds}s") from (e.__cause__ or e)


def bootstrap_pre_backend():
    """The guarded multi-host bootstrap, shared by ``paddle_tpu/__init__``
    and :func:`init_parallel_env` — the single home of the initialize-retry
    loop. Under a launcher (PADDLE_TRAINERS_NUM > 1, sentinel unset) brings
    up the JAX distributed runtime against coordinator
    ``PADDLE_TRAINER_ENDPOINTS[0]`` with retry/backoff; no-op otherwise.

    ``paddle_tpu/__init__`` calls this on a *standalone* importlib load of
    this module (registered under the canonical ``paddle_tpu.distributed.env``
    name, so the package import later reuses it) because importing the
    ``paddle_tpu.distributed`` package pulls in backend-touching modules,
    and jax requires initialize() before the first backend touch — the same
    before-any-kernel constraint as the reference's
    NCCLParallelContext::Init (nccl_context.cc:53).
    """
    if _INITIALIZED[0] or os.environ.get("_PADDLE_TPU_DIST_INITIALIZED"):
        _INITIALIZED[0] = True
        return
    if int(os.environ.get("PADDLE_TRAINERS_NUM", "1")) <= 1:
        return
    endpoints = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "").split(",")
    coordinator = (endpoints[0] or None) if endpoints else None
    num_processes = int(os.environ["PADDLE_TRAINERS_NUM"])
    process_id = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    # the CPU backend refuses multiprocess computations unless a CPU
    # collectives transport is selected, and the choice must land
    # before initialize(); TPU/GPU runs are unaffected (their
    # collectives ride ICI/NCCL, and any CPU-backend side computation
    # gets a working transport instead of INVALID_ARGUMENT)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    _initialize_distributed_with_retry(coordinator, num_processes, process_id)
    _LOG.info(
        "jax.distributed initialized: coordinator=%s process_id=%d "
        "num_processes=%d cohort_generation=%s",
        coordinator, process_id, num_processes,
        os.environ.get("PADDLE_TPU_COHORT_GEN", "0"))
    # env-var sentinel (not just module state): a re-exec or a second load
    # of this module in the same process must see the runtime as up
    os.environ["_PADDLE_TPU_DIST_INITIALIZED"] = "1"
    _INITIALIZED[0] = True


def init_parallel_env():
    """reference: distributed/parallel.py:60. Multi-host: initialize the JAX
    distributed runtime from the PADDLE_* env contract (normally already
    done by the pre-backend bootstrap in paddle_tpu/__init__, which routes
    through the same :func:`bootstrap_pre_backend`). Single-host: no-op."""
    bootstrap_pre_backend()
    _INITIALIZED[0] = True
    return ParallelEnv()


def get_rank(group=None):
    if _INITIALIZED[0] or int(os.environ.get("PADDLE_TRAINERS_NUM", "1")) > 1:
        return ParallelEnv().rank
    return 0


def get_world_size(group=None):
    if _INITIALIZED[0] or int(os.environ.get("PADDLE_TRAINERS_NUM", "1")) > 1:
        return ParallelEnv().world_size
    return 1


def is_initialized():
    return _INITIALIZED[0]


def device_count():
    return len(jax.devices())
