"""mxnet_tpu — a TPU-native framework with MXNet 1.x's capability surface.

Not a port: the compute path is jax/XLA/Pallas (SURVEY.md §7 design stance).
The public namespace mirrors ``import mxnet as mx`` so reference-era user
code (Gluon training loops, `mx.nd` scripting, KVStore DP) runs on TPU.
"""
from __future__ import annotations

__version__ = "0.1.0"

from .base import MXNetError, NotSupportedForTPUError  # noqa: F401
from .context import Context, cpu, gpu, tpu, current_context, num_gpus, num_tpus  # noqa: F401
from . import ndarray  # noqa: F401
from . import ndarray as nd  # noqa: F401
from .ndarray import NDArray  # noqa: F401
from . import autograd  # noqa: F401
from . import random  # noqa: F401
from . import initializer  # noqa: F401
from . import initializer as init  # noqa: F401
from . import optimizer  # noqa: F401
from . import lr_scheduler  # noqa: F401
from . import metric  # noqa: F401
from . import gluon  # noqa: F401
from . import kvstore  # noqa: F401
from . import kvstore as kv  # noqa: F401
from . import io  # noqa: F401
from . import parallel  # noqa: F401
from . import profiler  # noqa: F401
from . import runtime  # noqa: F401
from . import symbol  # noqa: F401
from . import symbol as sym  # noqa: F401
from .util import is_np_array  # noqa: F401

from .attribute import AttrScope  # noqa: F401
from . import models  # noqa: F401
from . import module  # noqa: F401
from . import module as mod  # noqa: F401
from . import operator  # noqa: F401
from . import rnn  # noqa: F401
from . import model  # noqa: F401
from . import monitor  # noqa: F401
from .monitor import Monitor  # noqa: F401
from . import visualization  # noqa: F401
from . import visualization as viz  # noqa: F401
from . import callback  # noqa: F401
from . import contrib  # noqa: F401
from . import image  # noqa: F401
from . import config  # noqa: F401

config.apply_compile_cache()  # persistent XLA compile cache placement

from . import observability  # noqa: F401
from . import inference  # noqa: F401
from . import observability as obs  # noqa: F401
from . import resilience  # noqa: F401
from . import test_utils  # noqa: F401
from .io import recordio  # noqa: F401

from .numpy_api import np, npx  # noqa: F401

# horovod compat is imported lazily (mxnet_tpu.horovod) to keep import light

