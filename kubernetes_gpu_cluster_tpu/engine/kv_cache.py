"""Paged KV cache: device-side page pool + host-side page allocator.

The reference relied on vLLM's PagedAttention block manager inside the CUDA
images and only exposed sizing knobs (``gpuMemoryUtilization``, ``maxModelLen``
— reference ``values-01-minimal-example8.yaml:26-27``, SURVEY C29). Here the
paged cache is native:

- Device side: one K and one V array of shape
  ``[num_layers, num_pages, page_size, num_kv_heads * head_dim]`` living in
  HBM. Layout rationale (TPU): the head dims are stored FLATTENED so the last
  (lane) dimension is >=128-aligned — Mosaic requires DMA slices aligned to
  the 128-lane tiling, and head_dim=64 models would violate it unflattened.
  A page slice ``[page_size, n_kv*hd]`` is the DMA unit the Pallas decode
  kernel streams HBM->VMEM. A single stacked array per K/V keeps jit donation
  trivial (the cache is donated every step, so updates alias in place).
- Host side: ``PageAllocator`` — a free-list allocator with optional
  copy-on-write-free refcounts, mirroring vLLM's block manager role. Page 0 is
  reserved as a scrap page: padding tokens write there so scatter updates need
  no masking inside jit.

Two-tier extension (``CacheConfig.swap_space_gb`` > 0): a SECOND page pool in
host DRAM (``HostKVPool``) plus batched device<->host transfer primitives
(``KVSwapper``), the vLLM swap-space role. Committed KV pages move to host
instead of being recomputed:

- scheduler preempt-by-swap (engine/scheduler.py): the victim's committed
  pages gather to host in one jitted batched gather, and readmission is a
  scatter + direct decode resume instead of a full re-prefill;
- prefix-spill: LRU-evicted ``PrefixCache`` pages spill to host, and
  ``lookup`` gets a second-chance host hit that restores the page.

Transfer discipline: the gather's device->host fetch COMPLETES inside
``swap_out`` — before the caller frees the pages and long before the next
step's dispatch consumes the donated pool (the KGCT004/KGCT010 contracts).
"""

from __future__ import annotations

import hashlib
import math
import time
from collections import OrderedDict
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import ModelConfig, CacheConfig
from ..resilience.faults import inject as _inject_fault
from ..utils import cdiv, get_logger
from ..utils.math import next_power_of_2

logger = get_logger("kv_cache")

# Page 0 never backs real tokens; padding slots scatter into it.
SCRAP_PAGE = 0
# State slot 0 belongs to no sequence: padding rows and absent segments
# update it.
SCRAP_SLOT = 0


class KVCache(NamedTuple):
    """Device-side paged KV pool. The layout comes from the model: K and V,
    each [L, P, page_size, n_kv * head_dim], or, for a latent-attention
    model, ONE pool ``k`` of rows [c | k_pe | pad] ([L, P, page_size,
    kv_row_padded]) and ``v`` None: there is no V to hold. L counts the
    layers that hold pages (``ModelConfig.num_kv_layers``).

    A state model's state layers keep a fixed SLOT a sequence beside the
    pages, in two more pools that ride with the page pools wherever those
    go (donated into every step program, returned by it): ``ssm`` [Ls,
    slots, *model.state_shape] float32, the recurrent state (``ops/ssm.py``
    and ``ops/kda.py`` have the layouts), and ``conv`` [Ls, slots,
    *model.state_conv_shape], the conv's last inputs, in the model's dtype.
    Slot 0 is scrap, as page 0 is. Both None for every other model. The
    two kinds are independent: a model of latent pages AND state layers
    holds ``k``, ``ssm`` and ``conv``."""
    k: jax.Array
    v: Optional[jax.Array]
    ssm: Optional[jax.Array] = None
    conv: Optional[jax.Array] = None
    # A sparse-attention model's index keys (``ops/dsa.py``): [Li, P,
    # page_size, index_head_dim], ONE key a token in each layer that holds
    # an indexer (``ModelConfig.index_layers``: Li of the L layers), on the
    # latent pool's page table: a page id names the same tokens in both.
    idx: Optional[jax.Array] = None

    @property
    def num_pages(self) -> int:
        return self.k.shape[1]

    @property
    def page_size(self) -> int:
        return self.k.shape[2]


def kv_cache_dtype(model: ModelConfig, cache: CacheConfig):
    """The pool's element type: ``cache.dtype`` when set, else the model's."""
    return jnp.dtype(cache.dtype) if cache.dtype else model.jnp_dtype


def allocate_kv_cache(
    model: ModelConfig,
    cache: CacheConfig,
    num_pages: int,
    sharding: Optional[jax.sharding.Sharding] = None,
    num_state_slots: int = 0,
) -> KVCache:
    """The page pools over the layers that hold pages and, for a state
    model, the two slot pools over its state layers (``num_state_slots``
    slots, the scrap slot among them)."""
    dtype = kv_cache_dtype(model, cache)
    shape = (model.num_kv_layers, num_pages, cache.page_size,
             model.kv_row_padded)
    def mk():
        return jnp.zeros(shape, dtype=dtype)
    if sharding is not None and model.kv_pools == 2:
        mk = jax.jit(mk, out_shardings=sharding)
    slots = {}
    if model.has_state:
        lead = (model.num_state_layers, num_state_slots)
        slots = dict(
            ssm=jnp.zeros(lead + model.state_shape, STATE_DTYPE),
            conv=jnp.zeros(lead + model.state_conv_shape, model.jnp_dtype))
    idx = None
    if model.index_topk:    # one key a token in each layer that chooses
        idx = jnp.zeros((len(model.index_layers), num_pages, cache.page_size,
                         model.index_head_dim), dtype)
    return KVCache(k=mk(), v=mk() if model.kv_pools == 2 else None, idx=idx,
                   **slots)


# The recurrent state is held and updated in float32: rounded to bfloat16 at
# every token of a 2 k-token context it is a different result (PERF.md
# section 4: part of the configuration, not a setting). The conv rows are
# copies of the model's activations and keep its dtype.
STATE_DTYPE = jnp.float32


def default_state_slots(model: ModelConfig, max_num_seqs: int) -> int:
    """The slots a state model's engine holds: a seat each and the scrap
    slot 0; none for a model without state layers."""
    return max_num_seqs + 1 if model.has_state else 0


def state_bytes_per_seq(model: ModelConfig) -> int:
    """Bytes one sequence's slot holds over all state layers: the state and
    the conv rows. 0 for a model without state layers."""
    if not model.has_state:
        return 0
    return model.num_state_layers * (
        math.prod(model.state_shape) * jnp.dtype(STATE_DTYPE).itemsize
        + math.prod(model.state_conv_shape) * model.jnp_dtype.itemsize)


def kv_cache_bytes_per_token(model: ModelConfig, cache: CacheConfig) -> int:
    """Bytes one cached token really holds over the layers that hold pages,
    padding of a latent row included (``kv_row_padding_share`` says how much
    of it), and a sparse-attention model's index keys beside them."""
    return ((model.kv_pools * model.num_kv_layers * model.kv_row_padded
             + index_cache_row(model)) * kv_cache_dtype(model, cache).itemsize)


def index_cache_row(model: ModelConfig) -> int:
    """Elements one cached token holds in the index-key pool, all its
    layers: 0 for a model without an indexer."""
    return len(model.index_layers) * model.index_head_dim


def kv_row_padding_share(model: ModelConfig) -> float:
    """Share of a stored row that is padding: 0 for K|V rows, 64/640 for a
    576-wide latent row in five 128-lane tiles. Reported, not hidden."""
    return 1.0 - model.kv_row_dim / model.kv_row_padded


def kv_cache_bytes_per_page(model: ModelConfig, cache: CacheConfig) -> int:
    return cache.page_size * kv_cache_bytes_per_token(model, cache)


def derive_num_pages(
    model: ModelConfig,
    cache: CacheConfig,
    max_model_len: int,
    max_num_seqs: int,
    hbm_free_bytes: Optional[int] = None,
) -> int:
    """Size the page pool. If ``cache.num_pages`` is set, use it; else use
    ``hbm_utilization`` of free HBM (the reference's gpuMemoryUtilization
    semantics); else fall back to enough pages for max_num_seqs full-length
    sequences (CPU/test path)."""
    if cache.num_pages is not None:
        return cache.num_pages
    if hbm_free_bytes is not None:
        budget = int(hbm_free_bytes * cache.hbm_utilization)
        n = budget // kv_cache_bytes_per_page(model, cache)
        if n < 2:
            raise ValueError(
                f"HBM budget {budget} too small for even 2 KV pages "
                f"({kv_cache_bytes_per_page(model, cache)} B/page)")
        return n
    pages_per_seq = cdiv(max_model_len, cache.page_size)
    return max_num_seqs * pages_per_seq + 1  # +1 scrap page


class PageAllocator:
    """Free-list page allocator with refcounts (enables future copy-on-write
    prefix sharing). All operations O(1) amortized. Host-side only — the device
    never sees this object, just the block tables it produces.

    The SAME manager hands out a state model's second kind of per-sequence
    memory: ``num_state_slots`` fixed slots of recurrent state (slot 0 is
    scrap, as page 0 is), one a sequence from admission to finish or
    preemption. A sequence is admitted only if its pages AND a slot fit
    (``can_admit``); a slot never grows and is never shared."""

    def __init__(self, num_pages: int, page_size: int,
                 num_state_slots: int = 0):
        assert num_pages >= 2, "need at least scrap page + 1 usable page"
        self.num_pages = num_pages
        self.page_size = page_size
        # Page 0 is the scrap page and never allocatable.
        self._free: list[int] = list(range(num_pages - 1, 0, -1))
        self._refcount: dict[int, int] = {}
        self.num_state_slots = num_state_slots
        self._free_slots: list[int] = list(range(num_state_slots - 1, 0, -1))

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_free_slots(self) -> int:
        return len(self._free_slots)

    def can_admit(self, n_pages: int) -> bool:
        """Whether a NEW sequence fits: its pages and, for a state model, a
        slot. The slot is asked first: ``can_allocate`` may evict."""
        if self.num_state_slots and not self._free_slots:
            return False
        return self.can_allocate(n_pages)

    def allocate_slot(self) -> Optional[int]:
        """A state slot for a sequence being admitted; None for a model
        that has none."""
        if not self.num_state_slots:
            return None
        if not self._free_slots:
            raise RuntimeError("state slots exhausted")
        return self._free_slots.pop()

    def free_slot(self, slot: Optional[int]) -> None:
        if slot is not None:
            if slot in self._free_slots or not 0 < slot < self.num_state_slots:
                raise RuntimeError(f"double free of state slot {slot}")
            self._free_slots.append(slot)

    def can_allocate(self, n: int) -> bool:
        return len(self._free) >= n

    def allocate(self, n: int) -> list[int]:
        if not self.can_allocate(n):
            raise RuntimeError(f"KV page pool exhausted: want {n}, free {self.num_free}")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refcount[p] = 1
        return pages

    def fork(self, page: int) -> None:
        """Increment refcount (copy-on-write prefix sharing)."""
        self._refcount[page] += 1

    def free(self, pages: list[int]) -> None:
        for p in pages:
            rc = self._refcount.get(p)
            if rc is None:
                raise RuntimeError(f"double free of page {p}")
            if rc == 1:
                del self._refcount[p]
                self._free.append(p)
            else:
                self._refcount[p] = rc - 1

    def pages_for_tokens(self, num_tokens: int) -> int:
        return cdiv(num_tokens, self.page_size)


class HostKVPool:
    """Second KV tier: a page pool in host DRAM, sized by
    ``CacheConfig.swap_space_gb``. Same ``[L, P, page_size, kv_dim]`` layout
    as the device pool so a page moves as one contiguous fancy-index copy.
    ``np.zeros`` backing means untouched pages cost only virtual memory —
    the RSS bill arrives page-by-page as swap traffic actually lands. The
    memory is ordinary pageable host memory (numpy offers no page-locked
    allocation); page-locking the pool for faster DMA staging is open work
    for the TPU capture (ROADMAP item 2)."""

    def __init__(self, num_pages: int, num_layers: int, page_size: int,
                 kv_dim: int, dtype):
        assert num_pages >= 1, "host pool needs at least one page"
        self.num_pages = num_pages
        self.k = np.zeros((num_layers, num_pages, page_size, kv_dim), dtype)
        self.v = np.zeros_like(self.k)
        self._free: list[int] = list(range(num_pages - 1, -1, -1))

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_in_use(self) -> int:
        return self.num_pages - len(self._free)

    def can_allocate(self, n: int) -> bool:
        return len(self._free) >= n

    def allocate(self, n: int) -> list[int]:
        if not self.can_allocate(n):
            raise RuntimeError(
                f"host KV pool exhausted: want {n}, free {self.num_free}")
        return [self._free.pop() for _ in range(n)]

    def free(self, pages: list[int]) -> None:
        self._free.extend(pages)

    def put(self, pages: list[int], k_np: np.ndarray, v_np: np.ndarray) -> None:
        idx = np.asarray(pages, np.int64)
        self.k[:, idx] = k_np
        self.v[:, idx] = v_np

    def get(self, pages: list[int]) -> tuple[np.ndarray, np.ndarray]:
        idx = np.asarray(pages, np.int64)
        return self.k[:, idx], self.v[:, idx]


class KVTransferPrograms:
    """The one jitted gather/scatter pair behind every KV transfer seam —
    :class:`KVSwapper` (device<->host tier) and :class:`KVPageIO`
    (cross-replica handoff) share a single instance, so a decode replica
    with ``swap_space_gb > 0`` compiles ONE gather and ONE scatter family,
    not two identical copies, and any future change to the transfer
    discipline lands in one place.

    One batched GATHER collects pages from the device pool into a
    contiguous ``[L, n_pad, ps, kd]`` transfer buffer, and one batched
    SCATTER (pool donated — XLA updates it in place, like every step
    program) writes them back. Page-count inputs are padded to powers of
    two with padding rows routed to ``SCRAP_PAGE`` (which never backs real
    tokens — a padded write is harmless by construction), so each direction
    compiles at most ``log2(max pages/seq)`` variants — inside the bounded
    bucket grid tests/test_compile_guard.py pins. Both programs compile
    lazily: engines that never transfer never pay.
    """

    def __init__(self, jit_enabled: bool = True, kv_sharding=None):
        def gather(k, v, idx):
            return k[:, idx], v[:, idx]

        def scatter(k, v, idx, k_data, v_data):
            return k.at[:, idx].set(k_data), v.at[:, idx].set(v_data)

        if jit_enabled:
            self._gather_fn = jax.jit(gather)
            out_s = (kv_sharding, kv_sharding) if kv_sharding is not None \
                else None
            self._scatter_fn = jax.jit(scatter, donate_argnums=(0, 1),
                                       out_shardings=out_s)
        else:
            self._gather_fn = gather
            self._scatter_fn = scatter

    def _padded_idx(self, pages: list[int]) -> np.ndarray:
        idx = np.full(next_power_of_2(len(pages)), SCRAP_PAGE, np.int32)
        idx[:len(pages)] = pages
        return idx

    def gather_pages(self, kv: "KVCache",
                     pages: list[int]) -> tuple[np.ndarray, np.ndarray]:
        """Gather ``pages`` into one contiguous host buffer pair ``(k, v)``
        of shape ``[L, n, ps, kd]``. The fetch COMPLETES inside this call
        (``np.asarray``): after return the device pages are free to be
        released and reallocated (KGCT010)."""
        n = len(pages)
        k_g, v_g = self._gather_fn(kv.k, kv.v, self._padded_idx(pages))
        return np.asarray(k_g)[:, :n], np.asarray(v_g)[:, :n]

    def scatter_pages(self, kv: "KVCache", device_pages: list[int],
                      k_np: np.ndarray, v_np: np.ndarray) -> "KVCache":
        """Scatter a host buffer pair into ``device_pages`` and return the
        rebound pool. The input pool is DONATED — the caller must rebind
        the result via its ``set_kv`` seam before any other consumer runs
        (schedule-time only, like a step program's pool; KGCT004)."""
        n = len(device_pages)
        idx = self._padded_idx(device_pages)
        L, _, ps, kd = kv.k.shape
        k_data = np.zeros((L, len(idx), ps, kd), kv.k.dtype)
        v_data = np.zeros_like(k_data)
        k_data[:, :n] = k_np
        v_data[:, :n] = v_np
        new_k, new_v = self._scatter_fn(kv.k, kv.v, idx, k_data, v_data)
        return KVCache(k=new_k, v=new_v)


class KVSwapper:
    """Device<->host page movement for the two-tier KV cache, on the shared
    :class:`KVTransferPrograms` gather/scatter pair.

    Ordering contracts (KGCT010 polices the static half):

    - ``swap_out`` returns only after ``np.asarray`` fully fetched the
      gather — the caller may free the device pages immediately after, and
      the next step's dispatch may consume the donated pool.
    - ``swap_in``/``restore_page`` scatter through ``get_kv``/``set_kv`` and
      must only run when no dispatched program is in flight (the engine's
      schedule-time paths satisfy this; the donated input is dead the moment
      the call returns, exactly like a step program's pool).

    Padding rows of both transfers are routed to ``SCRAP_PAGE``, which never
    backs real tokens — a padded scatter write is harmless by construction.
    """

    def __init__(self, host_pool: HostKVPool,
                 get_kv: Callable[[], "KVCache"],
                 set_kv: Callable[["KVCache"], None],
                 obs=None, jit_enabled: bool = True, kv_sharding=None,
                 programs: Optional[KVTransferPrograms] = None):
        self.host = host_pool
        self._get_kv = get_kv
        self._set_kv = set_kv
        self.obs = obs
        # Optional host-tier reclaim hook (the prefix-spill store registers
        # one): asked to drop LRU spilled entries when a swap-out needs room
        # — live-session KV outranks re-computable spilled prefixes.
        self.reclaim = None
        # Optional restore notification (the KGCT_SANITIZE KV-slot shadow
        # registers one): a swapped-in slot is committed history.
        self.on_restored = None
        self.programs = programs if programs is not None else \
            KVTransferPrograms(jit_enabled=jit_enabled,
                               kv_sharding=kv_sharding)

    def _emit(self, direction: str, pages: int, dt: float,
              request_id: str) -> None:
        if self.obs is not None:
            self.obs.on_swap(direction, pages, dt, request_id)

    def swap_out(self, pages: list[int], request_id: str = "") -> list[int]:
        """Gather ``pages`` from the device pool into host pages; returns
        the host page ids. Raises when the host tier has no room even after
        reclaim — the caller degrades to recompute-preemption. Chaos site
        ``kv_swap_fail`` (KGCT_FAULT) forces that path deterministically."""
        if _inject_fault("kv_swap_fail"):
            raise RuntimeError("KGCT_FAULT kv_swap_fail: injected swap-out "
                               "failure")
        n = len(pages)
        if not self.host.can_allocate(n) and self.reclaim is not None:
            self.reclaim(n - self.host.num_free)
        if not self.host.can_allocate(n):
            raise RuntimeError(
                f"host KV pool full: want {n}, free {self.host.num_free}")
        t0 = time.perf_counter()
        # Fetch COMPLETES inside gather_pages: after this line the device
        # pages are free to be reallocated.
        k_np, v_np = self.programs.gather_pages(self._get_kv(), pages)
        host_pages = self.host.allocate(n)
        self.host.put(host_pages, k_np, v_np)
        self._emit("out", n, time.perf_counter() - t0, request_id)
        return host_pages

    def swap_in(self, host_pages: list[int], device_pages: list[int],
                request_id: str = "") -> None:
        """Scatter host pages back into freshly allocated device pages and
        release the host copies. The device pool is donated through the
        scatter and rebound via ``set_kv`` before return."""
        n = len(host_pages)
        assert n == len(device_pages)
        t0 = time.perf_counter()
        k_np, v_np = self.host.get(host_pages)
        self._set_kv(self.programs.scatter_pages(
            self._get_kv(), device_pages, k_np, v_np))
        self.host.free(host_pages)
        self._emit("in", n, time.perf_counter() - t0, request_id)

    # -- single-page convenience (prefix-spill) -----------------------------

    def spill_page(self, page: int) -> Optional[int]:
        """Best-effort single-page spill (prefix-cache eviction path): None
        when the host tier has no room — spill never evicts host entries,
        so session swap-outs keep priority over re-computable prefixes."""
        if not self.host.can_allocate(1):
            return None
        try:
            [hp] = self.swap_out([page])
            return hp
        except RuntimeError:
            return None   # chaos-injected or raced-full: drop, don't spill

    def restore_page(self, host_page: int, device_page: int) -> None:
        self.swap_in([host_page], [device_page])

    def free_host(self, host_pages: list[int]) -> None:
        if host_pages:
            self.host.free(host_pages)

    def notify_restored(self, seq) -> None:
        if self.on_restored is not None:
            self.on_restored(seq)


class KVPageIO:
    """Cross-REPLICA KV page movement: the export/import seam of
    disaggregated prefill/decode serving (DistServe-style). A prefill
    replica gathers a finished prefill's committed pages into one
    contiguous host buffer (``export_pages``); the decode replica scatters
    the transferred buffer into freshly allocated pages of its own pool
    (``import_pages``) and the sequence resumes decode directly — the
    swap-in path, never a prefill replay.

    Same transfer discipline as :class:`KVSwapper` (KGCT010/KGCT013),
    because it IS the same machinery — both seams delegate to one shared
    :class:`KVTransferPrograms` pair:

    - ``export_pages`` returns only after ``np.asarray`` fully fetched the
      gather — the caller may free the device pages immediately after;
    - ``import_pages`` donates the pool through the scatter and rebinds it
      via ``set_kv`` before return (schedule-time only, like swap-in).

    This class (with ``KVSwapper``) is the ONLY sanctioned device-fetch of
    the KV pool: the KGCT013 lint rule fails any ``np.asarray``/device-get
    of KV pool contents outside this module.
    """

    def __init__(self, get_kv: Callable[[], "KVCache"],
                 set_kv: Callable[["KVCache"], None],
                 programs: KVTransferPrograms):
        self._get_kv = get_kv
        self._set_kv = set_kv
        # Always the engine's shared pair (KVSwapper rides the same one):
        # a private fallback here would let the two seams' compile families
        # silently diverge.
        self.programs = programs

    def export_pages(self, pages: list[int]) -> tuple[np.ndarray, np.ndarray]:
        """Gather ``pages`` from the device pool into one contiguous host
        buffer pair ``(k, v)`` of shape ``[L, n, ps, kd]``. The fetch
        COMPLETES inside this call: after return the device pages are free
        to be released and reallocated."""
        return self.programs.gather_pages(self._get_kv(), pages)

    def import_pages(self, device_pages: list[int],
                     k_np: np.ndarray, v_np: np.ndarray) -> None:
        """Scatter a transferred buffer pair into freshly allocated device
        pages. Must only run when no dispatched program is in flight (the
        engine's schedule-time import path satisfies this); the donated
        pool is rebound via ``set_kv`` before return."""
        n = len(device_pages)
        assert k_np.shape[1] == n and v_np.shape[1] == n
        self._set_kv(self.programs.scatter_pages(
            self._get_kv(), device_pages, k_np, v_np))


def build_kv_swapper(model: ModelConfig, cache: CacheConfig, kv: "KVCache",
                     get_kv, set_kv, obs=None, jit_enabled: bool = True,
                     kv_sharding=None,
                     programs: Optional[KVTransferPrograms] = None
                     ) -> Optional[KVSwapper]:
    """Size the host tier from ``swap_space_gb`` and build the swapper; None
    (with a loud log) when the budget fits less than one page."""
    if not cache.kv_swap_enabled:
        return None
    bpp = kv_cache_bytes_per_page(model, cache)
    num_host = int(cache.swap_space_gb * (1 << 30)) // bpp
    if num_host < 1:
        logger.warning(
            "kv swap disabled: swap_space_gb=%.3f fits no page (%d B/page)",
            cache.swap_space_gb, bpp)
        return None
    L, _, ps, kd = kv.k.shape
    pool = HostKVPool(num_host, L, ps, kd, np.dtype(kv.k.dtype))
    logger.info("host KV tier: %d pages x %d tokens (%.2f GB swap space)",
                num_host, ps, cache.swap_space_gb)
    return KVSwapper(pool, get_kv, set_kv, obs=obs, jit_enabled=jit_enabled,
                     kv_sharding=kv_sharding, programs=programs)


class PrefixCache:
    """Automatic prefix caching: full prompt pages are content-addressed by a
    CHAINED digest (page i's key commits to all tokens 0..(i+1)*ps), so a new
    request whose prompt shares a page-aligned prefix with any previously
    served one reuses those KV pages instead of recomputing them — the
    vLLM `enable_prefix_caching` capability, TPU-shaped: a cache hit turns
    admission into a chunked prefill whose "history" is the shared pages, so
    no new kernel is needed.

    Ownership: the cache holds ONE refcount on every cached page (pages are
    append-only, so content can never change while a reference exists).
    Sequences that reuse a page fork it (+1). Eviction is LRU and drops only
    the cache's own reference; pages still used by live sequences survive
    until their refcount drains. Digests are blake2b-chained — no
    Python-hash collisions serving wrong context.

    Host spill tier (``swapper`` attached by the engine when the two-tier
    cache is on): eviction SPILLS the victim page to host DRAM before
    dropping it, and ``lookup`` gets a second-chance host hit — the page
    scatters back into a fresh device page and the chain walk continues, so
    a prefix squeezed out by page pressure costs a memcpy, not a re-prefill.
    Host entries are a flat LRU keyed by digest: an entry whose parent left
    the host tier becomes unreachable, drifts to the LRU head untouched, and
    is reclaimed under the next pressure — bounded, no subtree bookkeeping.

    Fleet tier (``fleet_spill`` hooked by the serving layer when
    ``--fleet-prefix-cache`` is on): when the HOST rung cannot take an
    evicted page (swap off, host pool full, transfer failure), the page is
    offered to a PEER replica's host tier before being dropped — the
    remote-spill rung of the eviction ladder. The hook gathers the page
    content itself (fetch completes inside the call, before the free —
    KGCT010) and must never raise; a peer-received page enters through
    :meth:`accept_host_entry`, keyed by the same chained digest, so the
    peer's own ``lookup`` second-chances it like any local spill.
    """

    def __init__(self, allocator: "PageAllocator"):
        self.allocator = allocator
        self._entries: "OrderedDict[bytes, int]" = OrderedDict()  # digest->page
        # digest -> child digests: a chained child is only reachable through
        # its parent, so eviction must take descendants along or they would
        # sit unreachable while pinning page references.
        self._children: dict[bytes, set] = {}
        self.hits = 0
        self.misses = 0
        # Host spill tier (two-tier KV cache). digest -> host page id;
        # ordered for LRU reclaim when the swapper asks for room back.
        self.swapper: Optional["KVSwapper"] = None
        self._host_entries: "OrderedDict[bytes, int]" = OrderedDict()
        self.host_hits = 0
        # Fleet remote-spill rung: callable(digest, page) -> bool, set via
        # LLMEngine.enable_fleet_spill when fleet caching is on. Called
        # ONLY when the local host rung could not take the page.
        self.fleet_spill = None

    def attach_swapper(self, swapper: "KVSwapper") -> None:
        self.swapper = swapper
        swapper.reclaim = self._reclaim_host

    def _reclaim_host(self, n_pages: int) -> int:
        """Drop LRU spilled entries so a session swap-out can land: spilled
        prefixes are re-computable, a preempted session's KV is not."""
        dropped = 0
        while dropped < n_pages and self._host_entries:
            digest, hp = self._host_entries.popitem(last=False)
            self.swapper.free_host([hp])
            dropped += 1
        return dropped

    def __len__(self) -> int:
        return len(self._entries)

    @staticmethod
    def _page_digests(token_ids: list[int], n_pages: int, ps: int):
        """Chained blake2b digest per full page, yielded lazily (a lookup
        that misses on page 0 must not hash a hundred-page prompt)."""
        raw = np.asarray(token_ids[:n_pages * ps], np.int32).tobytes()
        digest = b""
        for i in range(n_pages):
            h = hashlib.blake2b(digest, digest_size=16)
            h.update(raw[i * ps * 4:(i + 1) * ps * 4])
            digest = h.digest()
            yield digest

    def lookup(self, token_ids: list[int],
               max_tokens: Optional[int] = None,
               record_stats: bool = True) -> tuple[list[int], int]:
        """Longest page-aligned cached prefix of ``token_ids`` (capped at
        ``max_tokens``). Returns (forked page ids, matched token count) —
        caller owns one reference per returned page.

        ``record_stats=False`` keeps the hit/miss counters untouched: the
        fleet-cache EXPORT path serves a peer's fetch through the same walk,
        and counting those as local hits would poison the per-replica
        locality gauges (``kgct_router_replica_prefix_cache_hit_ratio``)
        the affinity router reads."""
        ps = self.allocator.page_size
        n = len(token_ids) // ps
        if max_tokens is not None:
            n = min(n, max_tokens // ps)
        pages: list[int] = []
        matched = 0
        parent = b""
        for digest in self._page_digests(token_ids, n, ps):
            page = self._entries.get(digest)
            if page is None:
                page = self._second_chance(digest, parent)
            if page is None:
                break
            self._entries.move_to_end(digest)       # LRU touch
            # Fork as we go (the caller's reference): a later host restore's
            # allocate() may evict OTHER device entries under pressure, and
            # already-matched pages must survive that on our refcount.
            self.allocator.fork(page)
            pages.append(page)
            matched += ps
            parent = digest
        if record_stats:
            if matched:
                self.hits += 1
            else:
                self.misses += 1
        return pages, matched

    def export_walk(self, token_ids: list[int], max_tokens: int
                    ) -> tuple[list, int]:
        """Chain walk for a PEER's fetch: returns (entries, matched) where
        each entry is ``("dev", page)`` — forked, the caller owns one
        reference and must free after its gather — or ``("host", hp)`` —
        the host-tier page id, to be READ IN PLACE from the host pool.
        Unlike ``lookup`` this never restores a spilled page into the
        device pool, never touches LRU order, and never bumps any counter:
        serving a peer must not mutate the owner's cache state or skew its
        locality telemetry."""
        ps = self.allocator.page_size
        n = min(len(token_ids) // ps, max_tokens // ps)
        entries: list = []
        matched = 0
        for digest in self._page_digests(token_ids, n, ps):
            page = self._entries.get(digest)
            if page is not None:
                self.allocator.fork(page)
                entries.append(("dev", page))
            else:
                hp = self._host_entries.get(digest)
                if hp is None:
                    break
                entries.append(("host", hp))
            matched += ps
        return entries, matched

    def peek(self, token_ids: list[int],
             max_tokens: Optional[int] = None) -> int:
        """Token count of the longest cached prefix of ``token_ids`` —
        counting live entries AND host-spilled second-chance entries —
        WITHOUT forking pages, restoring spills, touching LRU order, or
        recording stats. The fleet-cache pull gate reads it: what is
        already local (either tier) costs at most a memcpy and must never
        be pulled from a peer."""
        ps = self.allocator.page_size
        n = len(token_ids) // ps
        if max_tokens is not None:
            n = min(n, max_tokens // ps)
        matched = 0
        for digest in self._page_digests(token_ids, n, ps):
            if digest not in self._entries and \
                    digest not in self._host_entries:
                break
            matched += ps
        return matched

    def _second_chance(self, digest: bytes, parent: bytes) -> Optional[int]:
        """Host-tier hit: restore the spilled page into a fresh device page
        and re-enter it as a live cache entry (the allocate() below IS the
        cache's reference, like register's fork). None on host miss or when
        no device page can be found even after eviction."""
        if self.swapper is None:
            return None
        hp = self._host_entries.pop(digest, None)
        if hp is None:
            return None
        if not self.allocator.can_allocate(1):
            self.swapper.free_host([hp])
            return None
        [page] = self.allocator.allocate(1)
        self.swapper.restore_page(hp, page)
        self._entries[digest] = page
        if parent:
            self._children.setdefault(parent, set()).add(digest)
        self.host_hits += 1
        return page

    def register(self, token_ids: list[int], pages: list[int],
                 start_page: int = 0) -> None:
        """Register the full pages backing ``token_ids`` (a completed prompt
        prefill). First registration of a digest wins; already-cached pages
        are left alone (dedupe).

        ``start_page``: the pages cover the chain FROM that page index
        (a fleet-cache delta import ships only the tail the importer did
        not already hold); the digest chain still walks from token 0 —
        chained digests commit to the whole prefix by construction."""
        ps = self.allocator.page_size
        n = min(start_page + len(pages), len(token_ids) // ps)
        parent = b""
        for i, digest in enumerate(self._page_digests(token_ids, n, ps)):
            if i >= start_page and digest not in self._entries:
                page = pages[i - start_page]
                self.allocator.fork(page)           # the cache's reference
                self._entries[digest] = page
                if parent:
                    self._children.setdefault(parent, set()).add(digest)
            parent = digest

    def evict(self, n_pages: int) -> int:
        """Drop LRU entries (each with its now-unreachable descendants)
        until ``n_pages`` entries were dropped or the cache is empty.
        Freeing only releases the cache's reference — shared pages stay
        alive for their sequences."""
        dropped = 0
        while dropped < n_pages and self._entries:
            digest, _ = next(iter(self._entries.items()))  # LRU head
            dropped += self._drop_subtree(digest)
        return dropped

    def _drop_subtree(self, digest: bytes) -> int:
        dropped = 0
        stack = [digest]
        while stack:
            d = stack.pop()
            page = self._entries.pop(d, None)
            if page is None:
                continue
            spilled = False
            if self.swapper is not None and d not in self._host_entries:
                # Spill BEFORE the free: the gather must read the page while
                # the cache's reference still pins it (KGCT010). Best-effort
                # — a full host pool just drops the page as before.
                hp = self.swapper.spill_page(page)
                if hp is not None:
                    self._host_entries[d] = hp
                    spilled = True
            elif d in self._host_entries:
                spilled = True
            if not spilled and self.fleet_spill is not None:
                # Remote-spill rung: the host tier could not take the page
                # (swap off / host full / transfer failure) — offer it to a
                # peer's host tier before dropping. The hook gathers the
                # content itself and the gather completes inside the call,
                # before the free below (KGCT010); it never raises (the
                # serving layer bounds and best-efforts the push).
                self.fleet_spill(d, page)
            self.allocator.free([page])
            dropped += 1
            stack.extend(self._children.pop(d, ()))
        return dropped

    def accept_host_entry(self, digest: bytes, k_np: np.ndarray,
                          v_np: np.ndarray) -> bool:
        """Receive a PEER's remote-spilled page into the local host tier,
        keyed by its chained digest — the receiving half of the fleet
        eviction rung. The page becomes an ordinary ``_host_entries`` spill:
        a later ``lookup`` whose chain reaches the digest second-chances it
        back into the device pool exactly like a local spill. False (and no
        state change) when the host tier is off, full, or already holds the
        digest — remote spill never evicts local entries (local sessions
        and local spills outrank a peer's cold prefixes)."""
        if self.swapper is None:
            return False
        if digest in self._host_entries or digest in self._entries:
            return False
        host = self.swapper.host
        if not host.can_allocate(1):
            return False
        [hp] = host.allocate(1)
        host.put([hp], k_np, v_np)
        self._host_entries[digest] = hp
        return True


class CachingPageAllocator(PageAllocator):
    """PageAllocator that transparently evicts prefix-cache entries under
    pressure, so every existing can_allocate/allocate call site (scheduler
    admission, decode window growth, chunk growth) gets eviction for free."""

    def __init__(self, num_pages: int, page_size: int,
                 num_state_slots: int = 0):
        super().__init__(num_pages, page_size, num_state_slots)
        self.prefix_cache = PrefixCache(self)

    def can_allocate(self, n: int) -> bool:
        # Evicting an entry only frees its page when no live sequence shares
        # it, so keep evicting until satisfied or the cache runs dry.
        while len(self._free) < n and len(self.prefix_cache):
            if self.prefix_cache.evict(n - len(self._free)) == 0:
                break
        return len(self._free) >= n
