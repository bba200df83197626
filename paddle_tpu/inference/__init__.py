"""paddle.inference: standalone predictor over exported artifacts.

Reference: paddle/fluid/inference/api/analysis_predictor.h:82
(AnalysisPredictor: Config → create_predictor → input handles →
ZeroCopyRun :165) and paddle_infer Python API.

TPU design: the deployable artifact is the serialized StableHLO program
jit.save writes (*.pdmodel = jax.export payload, *.pdiparams = pickled
params) — the predictor deserializes and executes it WITHOUT the model's
Python code, the role AnalysisPredictor's ProgramDesc loading served. The
analysis pass pipeline (fusions, TRT subgraphs) has no equivalent here by
design: XLA compiles the whole program at load.
"""
from __future__ import annotations

import os
import pickle
from typing import Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp


class Config:
    """reference: paddle_infer.Config (api/paddle_analysis_config.h)."""

    def __init__(self, prog_file: Optional[str] = None,
                 params_file: Optional[str] = None):
        if prog_file and prog_file.endswith(".pdmodel"):
            prog_file = prog_file[:-len(".pdmodel")]
        self._prefix = prog_file
        self._params_file = params_file
        self._enable_memory_optim = True
        # "auto": honor a .pdsharding.json sidecar when one exists;
        # None: force replicated; dict: an explicit enable_sharding request
        self._sharding_request = "auto"

    def enable_sharding(self, mesh=None, mesh_axes=None, input_specs=None,
                        param_specs=None, devices=None):
        """Request GSPMD-partitioned execution (the TPU-era analog of the
        multi-device knobs this Config otherwise stubs out).

        Any argument left None is filled from the artifact's
        ``.pdsharding.json`` sidecar at load; an explicit ``mesh`` wins
        over ``mesh_axes`` + ``devices`` (which build a sub-mesh over the
        first ``prod(sizes)`` of ``devices``). Mismatches between the spec
        and the visible devices warn and fall back to replicated — see
        :mod:`paddle_tpu.serving.sharding`."""
        self._sharding_request = {
            "mesh": mesh, "mesh_axes": mesh_axes,
            "input_specs": input_specs, "param_specs": param_specs,
            "devices": devices,
        }
        return self

    def disable_sharding(self):
        """Force replicated single-device execution, ignoring any
        ``.pdsharding.json`` sidecar."""
        self._sharding_request = None
        return self

    def set_prog_file(self, path):
        self._prefix = path[:-len(".pdmodel")] if path.endswith(".pdmodel") \
            else path

    def prog_file(self):
        return (self._prefix or "") + ".pdmodel"

    # accepted-and-ignored GPU-era knobs (kept for ported deploy scripts)
    def enable_use_gpu(self, *a, **k):
        pass

    def disable_gpu(self):
        pass

    def switch_ir_optim(self, flag=True):
        pass

    def enable_memory_optim(self, flag=True):
        self._enable_memory_optim = flag

    def enable_tensorrt_engine(self, *a, **k):
        raise NotImplementedError(
            "TensorRT subgraphs are CUDA-era; XLA compiles the whole "
            "program on TPU")


class _IOHandle:
    """Zero-copy-style tensor handle (reference: ZeroCopyTensor)."""

    def __init__(self):
        self._value = None

    def copy_from_cpu(self, arr):
        self._value = jnp.asarray(arr)

    def reshape(self, shape):
        if self._value is not None:
            self._value = self._value.reshape(shape)

    def copy_to_cpu(self):
        return np.asarray(self._value)

    def shape(self):
        return list(self._value.shape) if self._value is not None else None


class Predictor:
    """reference: api/analysis_predictor.h:82 (Run :120 / ZeroCopyRun
    :165)."""

    def __init__(self, config: Config):
        prefix = config._prefix
        from jax import export as jax_export
        from ..serving.cache import default_cache
        with open(prefix + ".pdmodel", "rb") as f:
            self._exported = jax_export.deserialize(f.read())
        # compiled-callable cache keyed on (artifact, input shapes/dtypes):
        # batch-size churn stops recompiling — each signature costs one XLA
        # compile, shared across Predictors over the same artifact
        self._model_key = os.path.abspath(prefix)
        self._exec_cache = default_cache()
        with open(config._params_file or prefix + ".pdiparams", "rb") as f:
            blob = pickle.load(f)
        self._params = [jnp.asarray(p) for p in blob["params"]]
        self._n_out = blob.get("n_out")
        # in_avals flattens the params list + the real inputs
        n_in = blob.get("n_in")
        if n_in is None:
            n_in = len(self._exported.in_avals) - len(self._params)
        self._input_names = [f"x{i}" for i in range(max(n_in, 0))]
        self._inputs: Dict[str, _IOHandle] = {
            n: _IOHandle() for n in self._input_names}
        self._outputs: List[_IOHandle] = []
        # GSPMD partitioning: resolve the config request / sidecar into
        # per-input + per-param NamedShardings (None -> replicated path)
        self._sharding = self._resolve_sharding(config, prefix,
                                                max(n_in, 0))
        if self._sharding is not None:
            self._params = [jax.device_put(p, s) for p, s in
                            zip(self._params,
                                self._sharding.param_shardings)]

    def _resolve_sharding(self, config: Config, prefix: str, n_in: int):
        """Bind the Config's sharding request (or the artifact sidecar)
        to devices; warns and returns None on any mismatch so the
        predictor falls back to replicated execution."""
        from ..serving import sharding as _sh
        req = getattr(config, "_sharding_request", "auto")
        if req is None:
            return None
        side = _sh.load_sidecar(prefix)
        if req == "auto":
            if side is None:
                return None
            return _sh.resolve(side, n_inputs=n_in,
                               n_params=len(self._params))
        mesh = req.get("mesh")
        mesh_axes = req.get("mesh_axes") or (side.mesh_axes if side
                                             else None)
        if mesh is None and not mesh_axes:
            import warnings
            warnings.warn(
                "enable_sharding() given no mesh/mesh_axes and the "
                "artifact has no sharding sidecar; serving replicated")
            return None
        inputs = req.get("input_specs")
        if inputs is None and side is not None:
            inputs = side.inputs
        params = req.get("param_specs")
        if params is None and side is not None:
            params = side.params
        spec = _sh.ShardingSpec(mesh_axes or {"_explicit_mesh": 1},
                                inputs, params)
        return _sh.resolve(spec, mesh=mesh, devices=req.get("devices"),
                           n_inputs=n_in, n_params=len(self._params))

    @property
    def sharding(self):
        """The active :class:`~paddle_tpu.serving.sharding
        .ResolvedSharding`, or None when running replicated."""
        return self._sharding

    def get_input_names(self) -> List[str]:
        return list(self._input_names)

    def get_input_handle(self, name) -> _IOHandle:
        return self._inputs[name]

    def get_output_names(self) -> List[str]:
        return [f"out{i}" for i in range(len(self._outputs))]

    def get_output_handle(self, name) -> _IOHandle:
        return self._outputs[int(name.replace("out", ""))]

    def run(self, inputs=None):
        """Either positional (returns numpy list, reference Run) or via the
        input handles (reference ZeroCopyRun)."""
        if inputs is not None:
            xs = [jnp.asarray(a) for a in inputs]
        else:
            xs = [self._inputs[n]._value for n in self._input_names]
        outs = self._call_cached(xs)
        if self._n_out is not None:
            outs = outs[:self._n_out]
        self._outputs = []
        for o in outs:
            h = _IOHandle()
            h._value = o
            self._outputs.append(h)
        return [np.asarray(o) for o in outs]

    def _call_cached(self, xs):
        """Execute through the shape-keyed ExecutableCache: one AOT
        XLA compile per input signature (shape-polymorphic artifacts
        re-lower per shape otherwise), AOT so the executable is
        serializable into the persistent tier.

        Sharded predictors commit each input onto its NamedSharding and
        append the sharding token to the cache key — replicas over
        different device subsets share the process-wide default cache, so
        the token (which includes device ids) is what keeps their
        executables, and the unsharded 2-tuple keys, from colliding.

        The key is process-stable (artifact abspath + shape/dtype
        signature + sharding token, no ids), so it doubles as the
        persistent-store key: a restarted process loads the serialized
        executable instead of compiling, and with a warm store a whole
        fleet start performs zero XLA compiles for known signatures."""
        from ..serving.cache import signature_of
        sig = signature_of(xs)
        exported = self._exported

        if self._sharding is None:
            key = (self._model_key, sig)
        else:
            key = (self._model_key, sig, self._sharding.token)
            xs = [jax.device_put(x, s) for x, s in
                  zip(xs, self._sharding.input_shardings)]
        params = self._params

        def _compile():
            return jax.jit(lambda ps, *xargs: exported.call(
                ps, *xargs)).lower(params, *xs).compile()

        fn = self._exec_cache.get_or_compile(key, _compile,
                                             persist_key=repr(key))
        outs = fn(self._params, *xs)
        return list(outs) if isinstance(outs, (list, tuple)) else [outs]


def create_predictor(config: Config) -> Predictor:
    """reference: paddle_infer.create_predictor."""
    return Predictor(config)
