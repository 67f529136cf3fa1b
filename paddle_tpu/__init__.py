"""paddle_tpu: a TPU-native deep-learning framework with PaddlePaddle's
capabilities, built from scratch on JAX/XLA/Pallas.

Not a port: the reference (shengwenLeong/Paddle, a PaddlePaddle fork) builds a
~2.5M-LoC C++/CUDA stack (phi kernels, executors, NCCL ProcessGroups, CUDA
allocators); on TPU, XLA *is* the kernel library, executor, allocator and SPMD
partitioner. This package keeps the paddle-shaped user surface — eager
``Tensor``/``nn.Layer``/optimizers, ``fleet`` hybrid parallel,
``distributed.launch`` — on a functional JAX core, with Pallas kernels for the
fused-op hot paths and ``jax.sharding`` meshes for every parallelism axis.
"""
from __future__ import annotations

# core
from .core import dtype as _dtype_mod
from .core.dtype import (bfloat16, bool_, complex64, complex128, float16,
                         float32, float64, get_default_dtype, int8, int16,
                         int32, int64, promote_types, set_default_dtype, uint8)
from .core.tensor import Parameter, Tensor, to_tensor
from .core.random import seed, get_rng_state, set_rng_state
from .core import device
from .core.device import (get_device, set_device, is_compiled_with_cuda,
                          is_compiled_with_xpu)

# autograd
from .autograd import engine as _engine
from .autograd.engine import no_grad, enable_grad, is_grad_enabled, set_grad_enabled, grad

# ops — star-export the functional surface (paddle.* namespace)
from .ops import *  # noqa: F401,F403
from . import ops

bool = bool_  # paddle.bool

# subpackages (imported lazily below to keep import time sane)
from . import nn  # noqa: E402
from . import optimizer  # noqa: E402
from . import regularizer  # noqa: E402
from . import amp  # noqa: E402
from . import io  # noqa: E402
from . import vision  # noqa: E402
from . import audio  # noqa: E402
from . import hub  # noqa: E402
from . import onnx  # noqa: E402
from . import text  # noqa: E402
from . import jit  # noqa: E402
from . import parallel  # noqa: E402
from . import distributed  # noqa: E402
from . import utils  # noqa: E402
from . import profiler  # noqa: E402
from . import static  # noqa: E402
from . import incubate  # noqa: E402
from . import metric  # noqa: E402
from . import callbacks  # noqa: E402
from . import distribution  # noqa: E402
from . import fft  # noqa: E402
from . import geometric  # noqa: E402
from . import signal  # noqa: E402
from . import sparse  # noqa: E402
from . import quantization  # noqa: E402
from .framework import io as _framework_io  # noqa: E402
from .framework.io import save, load  # noqa: E402
from .hapi.model import Model  # noqa: E402
from .nn.parallel import DataParallel  # noqa: E402
from .utils.flags import get_flags, set_flags  # noqa: E402
from . import version  # noqa: E402


def finfo(dtype):
    """Float type info (reference ``paddle.finfo``): min/max/eps/tiny/
    bits/dtype over the jax-canonicalized type. ml_dtypes (bfloat16,
    float8_*) carry their own finfo, which numpy's rejects."""
    import ml_dtypes as _ml
    import numpy as _np

    from .core import dtype as _dt
    d = _np.dtype(_dt.to_jax_dtype(dtype))
    try:
        return _np.finfo(d)
    except ValueError:
        return _ml.finfo(d)


def iinfo(dtype):
    """Integer type info (reference ``paddle.iinfo``). NOTE: with x64
    disabled, int64 canonicalizes to int32 — the returned bounds reflect
    the type arithmetic actually runs in."""
    import numpy as _np

    from .core import dtype as _dt
    return _np.iinfo(_np.dtype(_dt.to_jax_dtype(dtype)))

__version__ = version.full_version


def disable_static(place=None):
    """Paddle 2.x starts in dynamic mode; this framework is always eager-first."""
    return None


def enable_static():
    raise NotImplementedError(
        "static Program mode is replaced by paddle_tpu.jit (jax tracing); "
        "see paddle_tpu.static for the introspection surface")


def in_dynamic_mode():
    return True


def is_grad_enabled_():
    return is_grad_enabled()


def summary(net, input_size=None, dtypes=None):
    from .hapi.summary import summary as _summary
    return _summary(net, input_size, dtypes)


def flops(net, input_size, custom_ops=None, print_detail=False):
    from .hapi.dynamic_flops import flops as _flops
    return _flops(net, input_size, custom_ops=custom_ops,
                  print_detail=print_detail)
