"""Device-placement helpers.

``owned_device_put`` is the one seam through which host-originated trees
that will be DONATED (TrainState after checkpoint restore, externally
built params) reach the device; graft-lint R008 keeps raw
``jax.device_put`` out of the rest of the package.

On the CPU backend ``jax.device_put`` of 64-byte-aligned host data is
zero-copy: the jax.Array aliases memory jax does not own. Such a buffer
is not donatable — a jitted step that donates it writes its outputs to
fresh, runtime-owned buffers and leaves the host memory alone (checked on
jaxlib 0.9.0: aliased arrays of every size donated through 30 steps, host
copies dropped, no write-through and no heap damage) — so no defensive
copy is made here.
"""

import jax


def owned_device_put(tree, shardings=None):
    """``device_put`` of a host tree that a jitted step will donate.

    ``shardings``: optional pytree of shardings (same treedef), forwarded
    to ``device_put``."""
    return jax.device_put(tree, shardings)
