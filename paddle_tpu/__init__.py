"""paddle_tpu: a TPU-native deep-learning framework with PaddlePaddle's
capabilities (reference surveyed in SURVEY.md), built on JAX/XLA/Pallas.

Public namespace mirrors `paddle.*`: tensor creation + math at top level,
paddle_tpu.nn, .optimizer, .amp, .jit, .static, .distributed, .vision, ...
"""
from __future__ import annotations

import warnings as _warnings

# Without jax_enable_x64, int64 requests silently execute as int32 (paddle's
# default int dtype is int64; the semantics are preserved modulo width).
_warnings.filterwarnings(
    "ignore", message=".*requested in astype is not available.*")
_warnings.filterwarnings(
    "ignore", message=".*Explicitly requested dtype.*is not available.*")
_warnings.filterwarnings(
    "ignore", message=".*donated buffers were not usable.*")

import jax as _jax

# Under a launcher/spawn (PADDLE_TRAINERS_NUM > 1) the distributed runtime
# must come up before the first XLA-backend touch. The retry loop lives in
# distributed/env.py (bootstrap_pre_backend); load the env module
# standalone under its canonical name rather than through the
# paddle_tpu.distributed package (whose import runs most of the
# framework's imports first) — the package's later `from .env import ...`
# reuses this sys.modules entry, keeping exactly one copy of the bootstrap.
import os as _os
if (int(_os.environ.get("PADDLE_TRAINERS_NUM", "1")) > 1
        and not _os.environ.get("_PADDLE_TPU_DIST_INITIALIZED")):
    import importlib.util as _ilu
    import sys as _sys
    _spec = _ilu.spec_from_file_location(
        "paddle_tpu.distributed.env",
        _os.path.join(_os.path.dirname(__file__), "distributed", "env.py"))
    _env_mod = _ilu.module_from_spec(_spec)
    _sys.modules["paddle_tpu.distributed.env"] = _env_mod
    _spec.loader.exec_module(_env_mod)
    _env_mod.bootstrap_pre_backend()
    del _spec, _env_mod, _ilu, _sys

# float32 ops must be float32-accurate (the reference computes true fp32 unless
# AMP is enabled). XLA's default runs f32 matmuls with bf16 passes on TPU;
# force full precision for f32 — the AMP/bf16 path (paddle_tpu.amp) is the MXU
# perf path and is unaffected by this setting.
_jax.config.update("jax_default_matmul_precision", "highest")

from .core import (  # noqa: F401
    Tensor, Parameter, no_grad, enable_grad, is_grad_enabled, set_grad_enabled,
    grad as _functional_grad, seed, get_rng_state, set_rng_state,
    set_default_dtype, get_default_dtype,
    set_flags, get_flags, set_device, get_device, device_count,
    CPUPlace, CUDAPlace, TPUPlace, Place,
    is_compiled_with_cuda, is_compiled_with_tpu,
    bool_ as bool8, uint8, int8, int16, int32, int64, float16, bfloat16,
    float32, float64, complex64, complex128,
)
from .core.dtypes import bool_  # noqa: F401

from .ops import *  # noqa: F401,F403
from .ops.dispatch import in_dygraph_mode, enable_static, disable_static  # noqa: F401
in_dynamic_mode = in_dygraph_mode  # reference: paddle/__init__.py:268 alias
from .ops import linalg  # noqa: F401
from .ops.linalg import cholesky, inverse, matrix_power  # noqa: F401
from . import tensor  # noqa: E402,F401
from .tensor import rank  # noqa: E402,F401

# grad function (paddle.grad)
grad = _functional_grad

from . import autograd  # noqa: E402,F401
from .autograd import PyLayer, PyLayerContext  # noqa: E402,F401

from . import nn  # noqa: E402,F401
from .ops import _late_alias as _ops_late_alias  # noqa: E402
_ops_late_alias()
from . import optimizer  # noqa: E402,F401
from . import regularizer  # noqa: E402,F401
from .nn.layer_base import ParamAttr  # noqa: E402,F401
from .nn.clip import (ClipGradByValue, ClipGradByNorm,  # noqa: E402,F401
                      ClipGradByGlobalNorm)
from . import jit  # noqa: E402,F401
from . import static  # noqa: E402,F401
from .framework_io import save, load  # noqa: E402,F401



def is_grad_enabled_():
    from .core import autograd_engine
    return autograd_engine.is_grad_enabled()


def disable_signal_handler():  # API parity no-op (reference: platform/init.cc:363)
    return None
from . import distributed  # noqa: E402,F401
from . import vision  # noqa: E402,F401
from . import models  # noqa: E402,F401
from .distributed import DataParallel  # noqa: E402,F401
from . import io  # noqa: E402,F401
from . import amp  # noqa: E402,F401
from . import profiler  # noqa: E402,F401
from . import observability  # noqa: E402,F401
from . import incubate  # noqa: E402,F401
from . import utils  # noqa: E402,F401
from . import text  # noqa: E402,F401
from . import quantization  # noqa: E402,F401
from . import inference  # noqa: E402,F401
from . import serving  # noqa: E402,F401
from . import sentinel  # noqa: E402,F401
from . import onnx  # noqa: E402,F401
from . import distribution  # noqa: E402,F401
from . import reader  # noqa: E402,F401
from .reader import batch  # noqa: E402,F401
from .hapi import callbacks  # noqa: E402,F401
from . import sysconfig  # noqa: E402,F401
from . import version  # noqa: E402,F401
# single source of truth for __version__: the reference-parity surface
# (version.py, v2.0-era snapshot) — pyproject's dist version is the
# package's own release number, deliberately distinct
__version__ = version.full_version
from .hapi import hub  # noqa: E402,F401
from . import metric  # noqa: E402,F401
from . import hapi  # noqa: E402,F401
from .hapi import Model, summary  # noqa: E402,F401
from .hapi.dynamic_flops import flops  # noqa: E402,F401
from .compat_surface import (  # noqa: E402,F401
    add_n, is_tensor, create_parameter, set_printoptions, scatter_,
    tanh_, is_compiled_with_xpu, is_compiled_with_npu,
    is_compiled_with_rocm, CUDAPinnedPlace, NPUPlace, XPUPlace,
    get_cudnn_version, get_cuda_rng_state, set_cuda_rng_state,
    ComplexTensor)
from numpy import dtype  # noqa: E402,F401  (paddle.dtype parity)
from .ops import reverse  # noqa: E402,F401  (late alias of flip)
from .core.dtypes import bool_ as bool  # noqa: E402,F401,A001
from .io import DataLoader  # noqa: E402,F401
