"""paddle_tpu — a TPU-native deep-learning framework with the capability
surface of PaddlePaddle (reference: /root/reference, snapshot 2024-10-24).

Design (see SURVEY.md §7): eager tensors + tape autograd over XLA:PJRT eager
dispatch; jit/static mode via jax tracing of the SAME ops; SPMD auto-parallel
over `jax.sharding.Mesh`; Pallas kernels for attention; the reference's 1.3M
LoC of CUDA kernels / allocators / stream executors are replaced by XLA.
"""
from __future__ import annotations

import time as _time

_IMPORT_T0 = _time.time_ns()     # the span clock: import.paddle_tpu, below

# Full dtype surface (int64/float64) as the reference has. Hot paths pass
# explicit f32/bf16/i32 dtypes, so TPU compute is unaffected by x64 mode.
import jax as _jax

_jax.config.update("jax_enable_x64", True)

# every later compilation (the rest of this import's included) becomes a
# compile.* span, and every collection of the garbage collector counts:
# four listeners registered, nothing started or compiled
from .observability import spans as _spans

_spans.watch_compiles()
_spans.watch_gc()

# -- core ---------------------------------------------------------------
from .core import dtypes as _dtypes
from .core.dtypes import (  # noqa: F401
    bool_ as bool_, uint8, int8, int16, int32, int64, float16, bfloat16,
    float32, float64, complex64, complex128, float8_e4m3fn, float8_e5m2,
    set_default_dtype, get_default_dtype,
)
from .core.tensor import Tensor, Parameter, to_tensor  # noqa: F401
from .core.engine import no_grad, enable_grad  # noqa: F401
from .core.random import seed, get_rng_state, set_rng_state  # noqa: F401

# -- ops (flat namespace, paddle-style) --------------------------------
from .tensor import *  # noqa: F401,F403
from .tensor import einsum  # noqa: F401

# -- subpackages --------------------------------------------------------
from . import amp  # noqa: F401
from . import autograd  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import io  # noqa: F401
from . import jit  # noqa: F401
from . import metric  # noqa: F401
from . import framework  # noqa: F401
from . import decomposition  # noqa: F401
from . import vision  # noqa: F401
from . import hapi  # noqa: F401
from . import models  # noqa: F401
from . import fft  # noqa: F401
from . import static  # noqa: F401
from . import inference  # noqa: F401
from . import incubate  # noqa: F401
from . import distribution  # noqa: F401
from . import sparse  # noqa: F401
from . import quantization  # noqa: F401
from . import observability  # noqa: F401
from . import profiler  # noqa: F401
from . import device  # noqa: F401
from . import audio  # noqa: F401
from . import geometric  # noqa: F401
from . import text  # noqa: F401
from . import onnx  # noqa: F401
from . import _C_ops  # noqa: F401
from . import signal  # noqa: F401
from . import regularizer  # noqa: F401
from . import sysconfig  # noqa: F401
from . import hub  # noqa: F401
from .batch import batch  # noqa: F401
from .hapi import Model  # noqa: F401
from .hapi import callbacks  # noqa: F401  (reference: paddle.callbacks)
from .framework import (  # noqa: F401
    save, load, set_device, get_device, device_count, is_compiled_with_cuda,
    is_compiled_with_xpu, is_compiled_with_rocm, in_dynamic_mode, CPUPlace,
    CUDAPlace, TPUPlace, get_flags, set_flags,
)
from .autograd import grad  # noqa: F401
from .nn.clip import ClipGradByGlobalNorm, ClipGradByNorm, ClipGradByValue  # noqa: F401

# paddle.disable_static/enable_static compatibility (we are always "dygraph";
# static mode == jit tracing)
def disable_static(place=None):
    return None


def enable_static():
    return None


def is_grad_enabled():
    from .core.engine import grad_enabled
    return grad_enabled()


def disable_signal_handler():
    return None


def device_guard(*a, **k):
    import contextlib
    return contextlib.nullcontext()


def LazyGuard():
    import contextlib
    return contextlib.nullcontext()


def summary(net, input_size=None, dtypes=None, input=None):
    from .hapi.summary import summary as _summary
    return _summary(net, input_size, dtypes=dtypes, input=input)


def flops(net, input_size, custom_ops=None, print_detail=False):
    return 0


__version__ = "0.1.0"


def __getattr__(name):
    """Fallback attribute resolution through the op table — ops that are
    deliberately not star-exported (e.g. `hash`, which would shadow the
    python builtin in `from paddle_tpu import *`) stay reachable as
    `paddle_tpu.<op>`, exactly like `_C_ops.<op>`."""
    from .core.tensor import _OPS_CACHE
    if name in _OPS_CACHE:
        return _OPS_CACHE[name]
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")


# set-up's first phase as a span: this file's first line to its last
_spans.add_span("import.paddle_tpu", "setup", _IMPORT_T0 * 1e-9,
                _spans.now())
