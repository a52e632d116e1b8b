"""Which code runs for a Pallas op here: the one decision, in one place.

Every op of this package that has a kernel (flash attention and its
partial form, the grouped matmul with the routed FFN around it, fused
GroupNorm) asks ``kernel_mode()`` and acts on the answer itself:

 - ``"tpu"``: the compiled kernel (per shard of the trainer's data axis,
   ``ops/batch_shard.py``);
 - ``"interpret"``: the same kernel in Pallas interpret mode (CPU tests);
 - ``"off"``: the op's jnp reference.

No caller turns the answer into an ``interpret=`` argument or a branch
of its own.  Two things set it:

 - ``ELASTICDL_FLASH`` = ``auto`` (the default: ``tpu`` on a TPU backend,
   ``off`` elsewhere) | ``tpu`` | ``interpret`` | ``off``.  The name is
   older than its reach: the benchmark's harness strips the switch by
   this name (ROADMAP D5).  Any other value raises.
 - ``kernels_off()``: a fact of the tracing context, like
   ``batch_shard.batch_axis``.  Code traced inside it sits where no
   Pallas call can be partitioned (auto mesh axes around a manual
   ``shard_map``, a model-parallel mesh, dimensions that do not divide
   the mesh), so every op takes its reference whatever the switch says.
"""

import contextlib
import contextvars
import os

import jax

SWITCH = "ELASTICDL_FLASH"
MODES = ("tpu", "interpret", "off")

_KERNELS_OFF = contextvars.ContextVar("elasticdl_kernels_off", default=False)


@contextlib.contextmanager
def kernels_off(here=True):
    """While tracing inside this block no Pallas kernel can run
    (``here`` False: the block changes nothing, for a caller whose
    condition is a value, e.g. ``mesh is not None``)."""
    token = _KERNELS_OFF.set(_KERNELS_OFF.get() or bool(here))
    try:
        yield
    finally:
        _KERNELS_OFF.reset(token)


def kernel_mode():
    """"tpu" (compiled), "interpret" or "off" for code traced here."""
    mode = os.environ.get(SWITCH, "auto")
    if mode == "auto":
        mode = "tpu" if jax.default_backend() == "tpu" else "off"
    elif mode not in MODES:
        raise ValueError(
            "%s=%r: want auto, tpu, interpret or off" % (SWITCH, mode))
    return "off" if _KERNELS_OFF.get() else mode


def resolve(interpret):
    """The mode an op runs in: ``kernel_mode()`` unless a test or
    ``chip_check.py`` forces the kernel with an explicit ``interpret``."""
    if interpret is None:
        return kernel_mode()
    return "interpret" if interpret else "tpu"
