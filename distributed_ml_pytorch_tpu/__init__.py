"""distributed_ml_pytorch_tpu — a TPU-native distributed training framework.

A brand-new JAX/XLA framework with the capabilities of the reference
``bkpcoding/distributed_ML_pytorch`` (a DistBelief/DownPour-SGD asynchronous
parameter-server trainer; see ``SURVEY.md``), re-designed TPU-first:

- **Sync data parallelism** over a ``jax.sharding.Mesh`` with compiled ``psum``
  gradient allreduce riding ICI (replaces the reference's out-of-tree gloo
  backend, ``example/main.py:165``).
- **Async DownPour-SGD parameter server** with ``n_push``/``n_pull`` cadence
  (reference ``asgd/optim/Asynchronous.py:42-71``) re-expressed functionally:
  jitted local steps + host-side tagged messaging between controller
  processes; the reference's Listener-thread data race becomes a race-free
  between-steps parameter swap.
- **Flax CNN models** (LeNet/AlexNet parity with ``example/models.py``, plus
  ResNet) and a CIFAR-10 pipeline.
- **p2p primitives** via ``ppermute`` (replaces ``pytorch_p2p_ex.py``).

Public API re-exports the contractual symbols recovered in SURVEY.md §2.3.
"""

from distributed_ml_pytorch_tpu.version import __version__
from distributed_ml_pytorch_tpu.utils.serialization import (
    ravel_model_params,
    unravel_model_params,
    make_unraveler,
)
from distributed_ml_pytorch_tpu.utils.messaging import (
    MessageCode,
    MessageListener,
    send_message,
)
from distributed_ml_pytorch_tpu.models import LeNet, AlexNet


def __getattr__(name):
    # contractual PS symbols (M1/M4/C1) — lazy to keep `import
    # distributed_ml_pytorch_tpu` light
    if name in ("ParameterServer", "Asynchronous", "DownpourSGD", "Listener"):
        from distributed_ml_pytorch_tpu.parallel import async_ps

        return getattr(async_ps, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "__version__",
    "ravel_model_params",
    "unravel_model_params",
    "make_unraveler",
    "MessageCode",
    "MessageListener",
    "send_message",
    "LeNet",
    "AlexNet",
    "ParameterServer",
    "Asynchronous",
    "DownpourSGD",
]
