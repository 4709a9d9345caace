"""TPU accelerator (reference ``accelerator/cuda_accelerator.py``
``CUDA_Accelerator`` — same seam, JAX/TPU semantics)."""

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.accelerator.abstract_accelerator import DeepSpeedAccelerator


class TPU_Accelerator(DeepSpeedAccelerator):

    def __init__(self):
        super().__init__()
        self._name = "tpu"
        self._communication_backend_name = "xla"  # ICI/DCN collectives via XLA
        self._current = 0
        self._seed = 0

    # -- device ---------------------------------------------------------
    def is_synchronized_device(self) -> bool:
        return False  # dispatch is async; jax.block_until_ready syncs

    def _devices(self):
        return jax.devices()

    def device_name(self, device_index: Optional[int] = None) -> str:
        if device_index is None:
            return "tpu"
        return f"tpu:{device_index}"

    def device(self, device_index: Optional[int] = None):
        return self._devices()[device_index if device_index is not None else self._current]

    def set_device(self, device_index: int) -> None:
        self._current = int(device_index)

    def current_device(self) -> int:
        return self._current

    def current_device_name(self) -> str:
        return self.device_name(self._current)

    def device_count(self) -> int:
        return jax.device_count()

    def synchronize(self, device_index: Optional[int] = None) -> None:
        # a tiny computation fenced to completion orders everything before it
        jax.block_until_ready(jnp.zeros((), jnp.float32))

    # -- RNG (the JAX model: explicit keys derived from one seed) --------
    def manual_seed(self, seed: int) -> None:
        self._seed = int(seed)

    manual_seed_all = manual_seed

    def initial_seed(self) -> int:
        return self._seed

    def get_rng_state(self, device_index: Optional[int] = None):
        return np.asarray(jax.random.PRNGKey(self._seed))

    def set_rng_state(self, new_state, device_index: Optional[int] = None) -> None:
        # a PRNGKey array: recover the seed fold (best effort — the JAX
        # model derives all randomness from keys the caller threads)
        self._seed = int(np.asarray(new_state).reshape(-1)[-1])

    # -- memory ---------------------------------------------------------
    def empty_cache(self) -> None:
        # XLA owns the arena; deleting unreachable buffers is the GC's job
        import gc
        gc.collect()

    def _stats(self, device_index):
        d = self.device(device_index)
        return getattr(d, "memory_stats", lambda: None)() or {}

    def memory_allocated(self, device_index: Optional[int] = None) -> int:
        return int(self._stats(device_index).get("bytes_in_use", 0))

    def max_memory_allocated(self, device_index: Optional[int] = None) -> int:
        return int(self._stats(device_index).get("peak_bytes_in_use",
                                                 self.memory_allocated(device_index)))

    def memory_stats(self, device_index: Optional[int] = None) -> dict:
        return dict(self._stats(device_index))

    def total_memory(self, device_index: Optional[int] = None) -> int:
        return int(self._stats(device_index).get("bytes_limit", 0))

    def available_memory(self, device_index: Optional[int] = None) -> int:
        s = self._stats(device_index)
        return int(s.get("bytes_limit", 0)) - int(s.get("bytes_in_use", 0))

    # -- dtype / capability ---------------------------------------------
    def is_bf16_supported(self) -> bool:
        return True

    def is_fp16_supported(self) -> bool:
        return True  # storage supported; bf16 is the native compute type

    def supported_dtypes(self):
        return [jnp.float32, jnp.bfloat16, jnp.float16, jnp.int8, jnp.int32]

    def is_available(self) -> bool:
        return any(d.platform == "tpu" for d in jax.devices())

    def communication_backend_name(self) -> str:
        return self._communication_backend_name

    # -- data movement ---------------------------------------------------
    def pin_memory(self, array):
        # host staging buffers: contiguity is what matters for DMA
        return np.ascontiguousarray(array)

    def on_accelerator(self, array) -> bool:
        try:
            return any(getattr(d, "platform", "") != "cpu"
                       for d in array.devices())
        except AttributeError:
            return False

    # -- op builders ------------------------------------------------------
    def op_builder_dir(self) -> str:
        return "deepspeed_tpu.ops.op_builder"

    def create_op_builder(self, class_name: str):
        cls = self.get_op_builder(class_name)
        return cls() if cls is not None else None

    def get_op_builder(self, class_name: str):
        import deepspeed_tpu.ops.op_builder as ob
        return getattr(ob, class_name, None) or ob.ALL_BUILDERS.get(class_name)
