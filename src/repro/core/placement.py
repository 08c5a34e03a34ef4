"""C4 — adaptive data placement: the DDIO/TPH decision, TPU edition.

Paper §III-D: DDIO blindly steering all device writes into the LLC hurts
NVM-backed regions (256 B access granularity → write amplification), so ORCA
(1) disables DDIO globally and (2) sets the PCIe TPH bit *per memory region*
— DRAM-backed regions go to the cache, NVM-backed regions go to memory.

TPU mapping (DESIGN.md §2): the analogous tiers are VMEM (the
software-managed "LLC"), HBM, and host memory (the capacity/persistence
tier standing in for NVM). The *decision problem* transfers intact: which
buffer class is staged where. This module is that decision table plus the
helpers that apply it:

* Pallas kernels consume :func:`memory_space_for` to pick BlockSpec memory
  spaces (VMEM staging vs ANY/HBM-resident operands);
* :class:`MemoryBudget` is the one ledger for the host DRAM + NVM tiers.
"""
from __future__ import annotations

import enum
from dataclasses import dataclass

VMEM_BYTES = 128 * 1024 * 1024  # v5e per-core VMEM ~128 MiB (we budget half)
VMEM_BUDGET = VMEM_BYTES // 2


class Tier(enum.Enum):
    VMEM = "vmem"  # hot, small: the DDIO/TPH->cache path
    HBM = "hbm"  # streaming: the TPH->memory (DRAM) path
    HOST = "host"  # cold/persistent: the NVM path (never cache-staged)


@dataclass(frozen=True)
class Region:
    """A registered memory region, as in RNIC memory registration."""

    name: str
    nbytes: int
    access_rate_hz: float = 0.0  # touches per engine step ~ per second
    persistent: bool = False  # needs to survive failure (NVM-like)
    streaming: bool = False  # written once, read once (DMA-like)


def classify(region: Region, vmem_left: int = VMEM_BUDGET) -> Tier:
    """The Fig. 5 decision, one region at a time.

    * persistent regions -> HOST (never pollute the cache tier; avoids the
      NVM write-amplification the paper measures);
    * hot small regions (doorbells, pointer buffers, ring headers) -> VMEM;
    * everything else (bulk tables, KV cache pages) -> HBM streaming.
    """
    if region.persistent:
        return Tier.HOST
    if region.nbytes <= vmem_left and region.access_rate_hz >= 1e3 and not region.streaming:
        return Tier.VMEM
    return Tier.HBM


def plan(regions: list[Region], vmem_budget: int = VMEM_BUDGET) -> dict[str, Tier]:
    """Greedy knapsack by access density (rate/byte), like LLC way allocation."""
    out: dict[str, Tier] = {}
    left = vmem_budget
    hot = sorted(
        (r for r in regions if not r.persistent),
        key=lambda r: -(r.access_rate_hz / max(r.nbytes, 1)),
    )
    for r in hot:
        t = classify(r, left)
        out[r.name] = t
        if t is Tier.VMEM:
            left -= r.nbytes
    for r in regions:
        if r.persistent:
            out[r.name] = Tier.HOST
    return out


def memory_space_for(tier: Tier):
    """BlockSpec memory space for a Pallas operand in this tier."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if tier is Tier.VMEM:
        return pltpu.VMEM
    return pl.ANY  # compiler-placed (HBM) — kernel DMAs tiles explicitly


def kernel_operand_spaces(regions: list[Region],
                          vmem_budget: int = VMEM_BUDGET) -> dict:
    """BlockSpec memory spaces for a kernel's operands, keyed by region name.

    The Pallas wrappers (hash_probe, paged_attention, embedding_reduce)
    declare one Region per operand — per-step staged blocks are small and
    hot, bulk walked or scattered arrays are streaming — and consume the
    same Fig. 5 decision the host-side placement applies: VMEM-tier regions
    become pipelined VMEM staging blocks, everything else stays
    compiler-placed (ANY/HBM), with the kernel's index maps doing the
    explicit tile DMA.
    """
    tiers = plan(regions, vmem_budget)
    return {name: memory_space_for(t) for name, t in tiers.items()}


def block_spaces(block_bytes: dict, bulk_bytes: dict,
                 vmem_budget: int = VMEM_BUDGET) -> dict:
    """Placement-fed BlockSpec memory spaces for a kernel's operands.

    ``block_bytes`` names per-grid-step staged blocks (small + hot — every
    step touches them: they get the VMEM/DDIO-to-cache treatment);
    ``bulk_bytes`` names bulk walked/scattered/aliased arrays (streaming —
    they stay compiler-placed and the kernel's index maps DMA tiles
    explicitly). The shared entry point for hash_probe's bucket walks and
    paged_attention's page-pool walk."""
    regions = [
        Region(n, nb, access_rate_hz=1e6) for n, nb in block_bytes.items()
    ] + [
        Region(n, nb, streaming=True) for n, nb in bulk_bytes.items()
    ]
    return kernel_operand_spaces(regions, vmem_budget)


def kvs_cache_bytes(cache_sets: int, cache_ways: int, key_words: int,
                    val_words: int) -> int:
    """Resident footprint of the KVS hot-set cache tier (keys + values +
    meta, int32, sentinel row included). ``kvstore.make`` checks this
    against :data:`VMEM_BUDGET` at build time — the cache is the one KVS
    region that must take the VMEM/DDIO-to-cache treatment whole, or the
    measured hit path degrades into another bulk walk."""
    return (cache_sets + 1) * cache_ways * (key_words + val_words + 1) * 4


class MemoryBudget:
    """One ledger for the paper's unified DRAM+NVM server-memory view.

    ORCA's fourth component sizes server memory as *one* pool built from
    DRAM and NVM and lets a single placement policy decide what lands on
    which side. Here the DRAM side ("dram") stands for device/host RAM
    holding live engine state plus evicted KV cold slabs, and the NVM side
    ("nvm") for the persistence tier the durability WAL streams into.
    Both consumers charge the same ledger:

    * ``serving.kv_cache.HostColdTier`` reserves ``cold:<slot>`` on store
      and releases on drop — eviction is refused when the budget is spent,
      not just when the tier's page array is full;
    * ``fault.recovery.DurabilityManager`` folds occupancy into the
      adaptive full-vs-delta split via :meth:`durability_threshold` — the
      fuller the pool, the more the flush policy prefers small deltas over
      full snapshots — and meters bytes via :meth:`note_write`.
    """

    def __init__(self, dram_bytes: int, nvm_bytes: int):
        self.capacity = {"dram": int(dram_bytes), "nvm": int(nvm_bytes)}
        self._used: dict[str, dict[str, int]] = {"dram": {}, "nvm": {}}
        self.bytes_written = {"dram": 0, "nvm": 0}

    def reserve(self, name: str, nbytes: int, side: str = "dram") -> bool:
        """Claim ``nbytes`` under ``name``; False (and no charge) if it
        doesn't fit or the name is already reserved on that side."""
        used = self._used[side]
        if name in used or self.used(side) + int(nbytes) > self.capacity[side]:
            return False
        used[name] = int(nbytes)
        return True

    def release(self, name: str, side: str = "dram") -> int:
        return self._used[side].pop(name, 0)

    def release_prefix(self, prefix: str, side: str = "dram") -> int:
        """Release every reservation whose name starts with ``prefix``
        (tier rebuild after crash recovery). Returns bytes freed."""
        used = self._used[side]
        victims = [n for n in used if n.startswith(prefix)]
        return sum(used.pop(n) for n in victims)

    def used(self, side: str = "dram") -> int:
        return sum(self._used[side].values())

    def free(self, side: str = "dram") -> int:
        return max(0, self.capacity[side] - self.used(side))

    def free_frac(self, side: str = "dram") -> float:
        cap = self.capacity[side]
        return 1.0 if cap <= 0 else self.free(side) / cap

    def note_write(self, nbytes: int, side: str = "nvm") -> None:
        """Meter streamed bytes (WAL appends / snapshot writes)."""
        self.bytes_written[side] += int(nbytes)

    def durability_threshold(self, base: float) -> float:
        """Adaptive dirty-fraction threshold under memory pressure.

        With a free pool the base threshold stands (full snapshots — and
        their shorter replay chains — are affordable). As DRAM occupancy
        rises (cold slabs crowding the pool), the threshold climbs toward
        1.0 so flushes prefer the smaller delta write: the same
        more-precious-when-fuller rule the cold tier applies to pages.
        """
        pressure = 1.0 - self.free_frac("dram")
        return float(min(1.0, base + (1.0 - base) * pressure))
