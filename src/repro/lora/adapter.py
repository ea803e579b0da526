"""LoRA adapter store: host-resident adapter weights + HBM slot table.

The device keeps ``max_adapters`` stacked slots (the layout the model's
multi-LoRA batching consumes: A (L, slots, d_in, r), B (L, slots, r, d_out)).
The FASTLIBRA cache manager decides *which* adapters are HBM-resident; this
store performs the physical host→device loads (slot writes) and maintains
the adapter→slot mapping the scheduler uses to build ``adapter_ids``.

Rank-dimension block paging (§4.3): an adapter of rank r occupies
``ceil(r / rank_block)`` unified-pool blocks; because all other dims match
the KV layout the pool never fragments. Padding ranks up to the slot rank is
TPU-friendly (slots are uniform, the SGMV kernel sees a static shape).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


@dataclasses.dataclass
class AdapterWeights:
    """One adapter's host-side weights per target: {t: (A, B)} numpy."""

    adapter_id: str
    rank: int
    weights: dict[str, tuple[np.ndarray, np.ndarray]]

    @property
    def nbytes(self) -> int:
        return sum(a.nbytes + b.nbytes for a, b in self.weights.values())


class AdapterStore:
    def __init__(self, model, max_slots: int, key: Optional[jax.Array] = None):
        self.model = model
        self.max_slots = max_slots
        key = key if key is not None else jax.random.PRNGKey(0)
        # device slot table (zeros = identity / no-op adapter)
        self.slots = jax.tree.map(
            lambda x: jnp.zeros_like(x), model.init_lora(key, max_slots)
        )
        self._host: dict[str, AdapterWeights] = {}
        self._slot_of: dict[str, int] = {}
        self._free_slots = list(range(max_slots - 1, -1, -1))

    # ----------------------------------------------------------------- host
    def register(self, adapter_id: str, key: jax.Array, scale: float = 0.01) -> AdapterWeights:
        """Create (or load) adapter weights into host memory."""
        if adapter_id in self._host:
            return self._host[adapter_id]
        lora = self.model.init_lora(key, 1)
        weights = {}
        for i, (t, (a, b)) in enumerate(lora.items()):
            # fold in the target's index, not hash(t): str hashes change
            # from process to process, and the weights must follow the key
            bkey = jax.random.fold_in(key, i)
            bmat = jax.random.normal(bkey, b[:, 0].shape, jnp.float32) * scale
            weights[t] = (np.asarray(a[:, 0]), np.asarray(bmat, np.float32))
        aw = AdapterWeights(adapter_id, self.model.cfg.lora.rank, weights)
        self._host[adapter_id] = aw
        return aw

    def host_bytes(self, adapter_id: str) -> int:
        return self._host[adapter_id].nbytes

    # --------------------------------------------------------------- device
    def slot_of(self, adapter_id: str) -> Optional[int]:
        return self._slot_of.get(adapter_id)

    def load(self, adapter_id: str) -> int:
        """Ensure the adapter occupies a device slot; returns the slot."""
        if adapter_id in self._slot_of:
            return self._slot_of[adapter_id]
        if not self._free_slots:
            raise RuntimeError(
                "no free adapter slots — evict via CacheManager first"
            )
        slot = self._free_slots.pop()
        aw = self._host[adapter_id]
        new_slots = dict(self.slots)
        for t, (a, b) in aw.weights.items():
            A, B = new_slots[t]
            A = A.at[:, slot].set(jnp.asarray(a, A.dtype))
            B = B.at[:, slot].set(jnp.asarray(b, B.dtype))
            new_slots[t] = (A, B)
        self.slots = new_slots
        self._slot_of[adapter_id] = slot
        return slot

    def unload(self, adapter_id: str) -> None:
        slot = self._slot_of.pop(adapter_id, None)
        if slot is None:
            return
        new_slots = dict(self.slots)
        for t, (A, B) in new_slots.items():
            new_slots[t] = (A, B.at[:, slot].set(0.0))
        self.slots = new_slots
        self._free_slots.append(slot)

    @property
    def resident(self) -> list[str]:
        return list(self._slot_of)
