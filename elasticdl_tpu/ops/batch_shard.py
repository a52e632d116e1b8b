"""Run a Pallas call once per shard of the trainer's data axis.

``CollectiveTrainer`` jits its step under plain GSPMD with the batch
sharded over one mesh axis.  A ``pallas_call`` is opaque to the
partitioner, and on JAX 0.9.0 a Mosaic kernel under a multi-device jit
does not lower at all ("Mosaic kernels cannot be automatically
partitioned. Please wrap the call in a shard_map." — my chip run, PR 21).
The kernels here are independent per batch row, so the trainer declares
its (mesh, axis) around the model's apply
(``batch_axis``) and the kernel wrappers route through ``per_batch_shard``,
which wraps the call in a ``shard_map`` over that axis.  Without a
declared axis the call is made directly: that is a single device, or the
model-parallel path, whose ``shard_map`` in
``parallel/ring_attention.py`` splits heads over ``tp`` as well as batch
over ``dp`` and so cannot be expressed as a batch axis alone.
"""

import collections
import contextlib
import contextvars

from jax import shard_map
from jax.sharding import PartitionSpec as P

_BATCH_AXIS = contextvars.ContextVar("elasticdl_batch_axis", default=None)
_ROOM = contextvars.ContextVar("elasticdl_device_room", default=None)

# One device's memory as the caller of ``batch_axis`` sees it: the
# backend's ``limit`` in bytes, and the bytes ``free`` once the caller's
# own state is on the device (for a trainer: parameters, optimizer state
# as sharded, gradients).  ``free`` == 0 says that an earlier statement
# proved too large (the step's compile ran out of memory): keep nothing.
DeviceRoom = collections.namedtuple("DeviceRoom", "limit free")


@contextlib.contextmanager
def batch_axis(mesh, axis, room=None):
    """Declare that, while tracing inside this block, the leading axis of
    every activation is sharded over ``axis`` of ``mesh``, and, where
    the backend states its memory, the ``DeviceRoom`` a device has for
    what is traced here (None: not stated, and code that would trade
    memory for time does what it did without)."""
    token = _BATCH_AXIS.set(None if mesh is None else (mesh, axis))
    room_token = _ROOM.set(room)
    try:
        yield
    finally:
        _ROOM.reset(room_token)
        _BATCH_AXIS.reset(token)


def device_room():
    """The ``DeviceRoom`` declared around the code traced here, or None."""
    return _ROOM.get()


def declared():
    """The (mesh, axis) declared around the code traced here, or None:
    for an op whose backward, traced after the block has closed, must
    run per shard as its forward did."""
    return _BATCH_AXIS.get()


def shards_of(axis):
    """How many shards ``axis`` has, a (mesh, axis) that
    :func:`declared` gave; 1 for None."""
    return 1 if axis is None else axis[0].shape[axis[1]]


def shards():
    """How many shards the declared batch axis has (1 without one)."""
    return shards_of(_BATCH_AXIS.get())


def per_batch_shard(fn, batched, replicated=()):
    """``fn(*batched, *replicated)``, per shard of the declared batch
    axis when there is one.  ``batched`` arrays and every output lead
    with the batch dimension; ``replicated`` arrays (affine parameters)
    are whole on every shard, and their cotangents are summed over the
    axis by ``shard_map``'s transpose."""
    count = shards()
    if count == 1:
        return fn(*batched, *replicated)
    mesh, axis = _BATCH_AXIS.get()
    rows = [a.shape[0] for a in batched]
    if any(n % count for n in rows):
        # Called directly the kernel would reach the partitioner, whose
        # refusal ("Mosaic kernels cannot be automatically partitioned")
        # says nothing of the cause.
        raise ValueError(
            "a Pallas kernel got leading dimensions %s under a batch "
            "axis %r of %d shards: they must be whole multiples of it "
            "(CollectiveTrainer pads every minibatch to one)"
            % (rows, axis, count)
        )
    return shard_map(
        fn, mesh=mesh,
        in_specs=(P(axis),) * len(batched) + (P(),) * len(replicated),
        out_specs=P(axis), check_vma=False,
    )(*batched, *replicated)
