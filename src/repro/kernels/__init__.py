"""Pallas kernels for the prover's hot loops (dispatched by
``repro.core.backend``): Poseidon ``permute``, ``ntt`` and the grand
product.  Each kernel package holds the kernel (``<name>.py``), its
shape adapter (``ops.py``) and the pure-jnp oracle (``ref.py``)."""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, *, name: str, **kwargs):
    """``pl.pallas_call`` under ``name``, whose kernel and index maps trace
    with 64-bit types off.

    ``name`` is the kernel's name in the compiled program, so that a device
    trace names the kernel, not only the jitted function around it.

    ``repro.core.field`` turns ``jax_enable_x64`` on for the whole process.
    Under it the integer literals in BlockSpec index maps, loop bounds and
    roll amounts trace as ``i64``, which the TPU compiler refuses.  The
    kernels compute on ``uint32`` only, so no value changes."""
    if not name:
        raise ValueError("every Pallas kernel needs a name")
    call = pl.pallas_call(kernel, name=name, **kwargs)

    def run(*args):
        with jax.enable_x64(False):
            return call(*args)
    return run


def on_mesh(fn, x, split: bool):
    """``fn(x)``, run per device when ``x`` is spread over a device mesh.

    The TPU compiler cannot partition a Pallas kernel, so a kernel fed an
    array that lives on several devices is wrapped in ``shard_map``.  With
    ``split`` (``fn`` treats every entry of the leading axes independently
    and only transforms the last one) each device runs ``fn`` on its own
    block; otherwise every device runs it on the whole array.  On one
    device this is just ``fn(x)``."""
    from jax.sharding import NamedSharding, PartitionSpec

    sharding = getattr(x, "sharding", None)
    if not isinstance(sharding, NamedSharding) or sharding.mesh.size == 1:
        return fn(x)
    spec = tuple(sharding.spec) + (None,) * (x.ndim - len(sharding.spec))
    if not split or spec[-1] is not None:
        spec = ()
    spec = PartitionSpec(*spec)
    return jax.shard_map(fn, mesh=sharding.mesh, in_specs=spec,
                         out_specs=spec, check_vma=False)(x)
