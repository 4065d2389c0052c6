"""Batched tiered-memory embedding serving runtime (paper §VI).

Fast tier: a device-resident buffer of embedding vectors (on TPU this is the
HBM software-managed buffer; gathers go through the Pallas row-gather kernel
when available).  Slow tier: the full embedding tables in host memory.  A
miss triggers an on-demand host->device fetch (O(10us) per the paper).

The residency engine is **array-backed and batched** — the hot path does no
per-key Python work:

* ``_slot_map``  (N,) int32 — key -> slot, -1 when not resident (the dense
  inverse of the old ``slot_of`` dict; host tables are materialised arrays,
  so the key space is exactly ``range(N)``).
* ``_slot_key``  (C,) int64 — slot -> key, -1 when free (with ``_slot_map``
  this forms the two-way residency invariant checked in tests).
* ``_last_use``  (C,) int64 — LRU ranks from a global clock; batched
  eviction ranks all victims in one ``argpartition`` pass.
* ``_admit_seq`` (C,) int64 — admission order (the eviction fallback the
  dict insertion order used to provide).
* ``_pf_flag``   (C,) bool — prefetched-and-not-yet-demanded, for the
  Fig. 14 hit attribution.

``lookup`` partitions a batch into hits/misses with one vectorized gather on
``_slot_map``, admits all misses at once (single fused scatter into the
device buffer), and serves working sets larger than the buffer straight from
the host tier.  The per-key seed implementation is preserved verbatim in
:mod:`repro.core.tiered_reference`; ``tests/test_tiered_equivalence.py``
proves both produce identical counters on a recorded trace.

Under ``policy="recmg"`` eviction is driven by the **array-backed priority
engine** (:mod:`repro.core.priority_engine`): the whole miss batch admits
through one ``admit_interleaved`` call that ranks every victim in a single
vectorized pass and resolves own-batch evictions (a just-admitted key
evicted by a later key of the same batch) without per-key Python.  The
seed-faithful per-key loop survives as ``_admit_recmg_sequential`` — the
equivalence oracle, also the safety net should the engine ever desync from
residency (checked per batch in O(1)).

The gather path is **device-resident end-to-end**: one jitted
``buf[idx][inv]`` fused gather per batch (both index vectors padded to
power-of-two shape buckets), overflow rows folded in through a jitted
``where``-select over staged host rows instead of a device->host->device
bounce, and no intermediate ``block_until_ready`` between the miss-path
scatter and the gather — fetch and gather pipeline inside one device sync
(``fetch_s`` therefore measures host-side admit + dispatch; execution time
lands in ``gather_s``).  ``warmup(batch_hint)`` (or the ``warmup_batch``
constructor argument) eagerly compiles every shape bucket a batch can hit,
so XLA compiles land at construction instead of inside measured batches;
the jitted functions are module-level, so all stores of one process share
one compile cache.

The buffer is co-managed by the RecMG models exactly as in Algorithms 1 & 2:
the caching model's bits set priorities of the just-accessed chunk, the
prefetch model's predictions are inserted ahead of use, both computed one
batch ahead (pipelined) on the CPU.  ``stage_model_outputs`` double-buffers
those outputs so they land at the next batch boundary without blocking an
in-flight ``lookup``.

Besides wall-clock measurement, the runtime reports an analytic latency
decomposition using the slow-tier cost model (fetch_us per missing row +
fixed per-batch overhead) so results transfer to the real two-tier hardware
this container lacks; the linear performance model of §VII-F (Fig. 18) is
fitted from these runs.  See ``docs/architecture.md`` for the full state
layout and invariants.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.buffer_manager import RecMGBuffer
from repro.obs.tracing import Steps, get_tracer


# Quantized fast-tier row formats: storage dtype per format (scale stays
# fp32 either way).  Mirrors repro.kernels.embedding_gather.ROW_FORMATS —
# kept local so the store's constructor-time validation doesn't import the
# Pallas stack.
_QDTYPE = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}


def fast_row_bytes(d: int, host_dtype, quantize: bool,
                   row_format: str = "int8") -> int:
    """Per-row fast-tier footprint in bytes: ``d * itemsize`` for fp32
    rows, ``d * 1 + 4`` for the quantized formats (1-byte elements + one
    fp32 scale) — the accounting the byte-budget facades split on."""
    if quantize:
        if row_format not in _QDTYPE:
            raise ValueError(f"unknown row_format {row_format!r} "
                             f"(expected one of {sorted(_QDTYPE)})")
        return d + 4
    return d * np.dtype(host_dtype).itemsize


def _bucket(n: int) -> int:
    """Round up to a power of two (>= 16): the shape-bucketing that keeps
    the jitted scatter/gather from recompiling for every working-set size."""
    return max(16, 1 << (int(n) - 1).bit_length())


# ---------------------------------------------------------------------------
# Module-level jitted scatter/gather: one compile cache per process, shared
# by every store instance (per-instance lambdas would recompile the same
# shape buckets once per table/shard).  ``inv`` folds the unique->request
# expansion into the same fused program, so the result never leaves the
# device; the ``_OV`` variants where-select staged host rows for overflow
# (working set larger than the buffer) without a host round-trip.
# ---------------------------------------------------------------------------

# ``iv`` packs both index vectors — row 0 the unique slots, row 1 the
# unique->request inverse — into one operand, so each gather costs a
# single host->device transfer.
_JIT_GATHER = jax.jit(lambda buf, iv: buf[iv[0]][iv[1]])
_JIT_GATHER_OV = jax.jit(
    lambda buf, iv, ov, hr: jnp.where(ov[:, None], hr, buf[iv[0]])[iv[1]])
_JIT_GATHER_Q = jax.jit(
    lambda buf, sc, iv:
    (buf[iv[0]].astype(jnp.float32) * sc[iv[0]][:, None])[iv[1]])
_JIT_GATHER_Q_OV = jax.jit(
    lambda buf, sc, iv, ov, hr:
    jnp.where(ov[:, None], hr,
              buf[iv[0]].astype(jnp.float32) * sc[iv[0]][:, None])[iv[1]])

# The writes into the fast tier run as programs named ``store_write*``
# (``jit_store_write``, ``jit_store_write_quant``, ...), so a profile finds
# them by name.
def store_write(buf, idx, rows):
    """Scatter admitted rows into their slots of the fast tier."""
    return buf.at[idx].set(rows)


_JIT_STORE_WRITE = jax.jit(store_write, donate_argnums=(0,))


def store_write_quant(buf, sc, idx, rows, row_format):
    """Fused device-side quantize + scatter: per-row scale derivation,
    round/clip and both buffer writes trace into ONE jitted program, so
    the quantized admit keeps the fp32 path's single-dispatch /
    one-sync-per-batch property (the old host NumPy quantizer serialized
    a round-trip per admit)."""
    from repro.kernels.embedding_gather import quantize_rows_ref

    q, s = quantize_rows_ref(rows, row_format)
    return buf.at[idx].set(q), sc.at[idx].set(s)


_JIT_STORE_WRITE_Q = jax.jit(store_write_quant, static_argnums=(4,),
                             donate_argnums=(0, 1))

_KERNEL_JITS: Dict[tuple, object] = {}


def kernel_gather_ok(backend: str, d: int, fast_dtype) -> bool:
    """Whether the compiled Pallas row gather can serve a fast tier of
    ``fast_dtype`` rows of width ``d`` on ``backend``: a TPU, D a multiple
    of 128 (the lane width) and 32-bit rows.  The TPU compiler refuses a
    one-row DMA of 1-byte rows (they pack four to a sublane), so a
    quantized fast tier takes the XLA gather and quantizer on the TPU
    (docs/architecture.md, "The quantized fast tier")."""
    return (backend == "tpu" and d % 128 == 0
            and np.dtype(fast_dtype).itemsize == 4)


def _n_unique(iv):
    """Unique-row count of a packed ``(2, bucket)`` gather operand: the
    unique->request inverse (row 1) is onto ``range(u)`` and its padding
    is 0, so ``u = max(inverse) + 1``."""
    return jnp.max(iv[1]) + 1


def _kernel_gathers(quantized: bool = False, interpret: bool = False):
    """Pallas row-gather variants, built lazily (TPU backend, or any
    backend under ``interpret=True``).  The fp32 pair copies only the
    ``u`` unique rows of the bucket; ``quantized=True`` returns the
    dequantizing pair (interpret mode only, see :func:`kernel_gather_ok`).
    Both fold the overflow where-select and the unique->request expansion
    into the same program."""
    key = ("gq" if quantized else "g", interpret)
    if key not in _KERNEL_JITS:
        from repro.kernels import embedding_gather as eg

        if quantized:
            def g(buf, sc, iv, _i=interpret):
                return eg.gather_rows_dequant(buf, sc, iv[0],
                                              interpret=_i)[iv[1]]

            def gov(buf, sc, iv, ov, hr, _i=interpret):
                return jnp.where(
                    ov[:, None], hr,
                    eg.gather_rows_dequant(buf, sc, iv[0],
                                           interpret=_i))[iv[1]]
        else:
            def g(buf, iv, _i=interpret):
                return eg.gather_rows(buf, iv[0], _n_unique(iv),
                                      interpret=_i)[iv[1]]

            def gov(buf, iv, ov, hr, _i=interpret):
                return jnp.where(ov[:, None], hr,
                                 eg.gather_rows(buf, iv[0], _n_unique(iv),
                                                interpret=_i))[iv[1]]
        _KERNEL_JITS[key] = (jax.jit(g), jax.jit(gov))
    return _KERNEL_JITS[key]


def _kernel_scatter_q(row_format: str, interpret: bool = False):
    """Fused Pallas quantize + scatter for the kernel path: admitted fp32
    rows are quantized by the :func:`~repro.kernels.embedding_gather.
    quantize_rows` kernel and scattered into the quantized buffer + scale
    vector inside one jitted program (single dispatch, donated buffers)."""
    key = ("qs", row_format, interpret)
    if key not in _KERNEL_JITS:
        from repro.kernels import embedding_gather as eg

        def store_write_quant_kernel(buf, sc, idx, rows, _rf=row_format,
                                     _i=interpret):
            q, s = eg.quantize_rows(rows, row_format=_rf, interpret=_i)
            return buf.at[idx].set(q), sc.at[idx].set(s)

        _KERNEL_JITS[key] = jax.jit(store_write_quant_kernel,
                                    donate_argnums=(0, 1))
    return _KERNEL_JITS[key]


@dataclass
class TierStats:
    batches: int = 0
    lookups: int = 0
    hits: int = 0
    misses: int = 0  # request-level fast-tier misses (hits + misses == lookups)
    prefetch_hits: int = 0
    on_demand_rows: int = 0
    evictions: int = 0
    fetch_s: float = 0.0  # miss path: slow_read_s + residency_s + write_s
    gather_s: float = 0.0  # gather_dispatch_s + sync_s
    model_s: float = 0.0  # applying RecMG outputs: rank_s + prefetch_s
    modeled_fetch_s: float = 0.0  # analytic slow-tier penalty
    # Steps of a lookup and of applying model outputs, in host seconds,
    # always on (one perf_counter reading per boundary), then counts:
    partition_s: float = 0.0  # unique, slot map, hit/miss, pf hits, touch
    slow_read_s: float = 0.0  # host read of the missed rows
    residency_s: float = 0.0  # admission and eviction
    write_s: float = 0.0  # dispatch of the write into the fast tier
    gather_dispatch_s: float = 0.0  # gather operands + dispatch
    sync_s: float = 0.0  # device sync of the lookup's result
    rank_s: float = 0.0  # RecMG priorities of the accessed chunks
    prefetch_s: float = 0.0  # RecMG prefetch admission and priorities
    populate_calls: int = 0  # apply_model_outputs calls
    rank_passes: int = 0  # engine passes that ranked trunks
    write_rows: int = 0  # rows written into the fast tier (before padding)
    overflow_rows: int = 0  # rows served through the overflow select
    h2d_bytes: int = 0  # bytes of the host arrays lookups and writes send

    SECONDS = ("partition_s", "slow_read_s", "residency_s", "write_s",
               "gather_dispatch_s", "sync_s", "rank_s", "prefetch_s")
    COUNTS = ("populate_calls", "rank_passes", "write_rows", "overflow_rows",
              "h2d_bytes")

    @property
    def hit_rate(self):
        return self.hits / max(self.lookups, 1)

    def as_dict(self):
        # ``hits`` is emitted raw alongside the rounded ``hit_rate``:
        # serve/bench JSON must stay lossless for cross-run aggregation
        # (summing rounded rates across runs is meaningless).
        return {
            "batches": self.batches, "lookups": self.lookups,
            "hits": self.hits, "misses": self.misses,
            "hit_rate": round(self.hit_rate, 4),
            "prefetch_hits": self.prefetch_hits,
            "on_demand_rows": self.on_demand_rows,
            "evictions": self.evictions,
            "fetch_s": round(self.fetch_s, 4),
            "gather_s": round(self.gather_s, 4),
            "model_s": round(self.model_s, 4),
            "modeled_fetch_s": round(self.modeled_fetch_s, 4),
        }

    def steps(self) -> Dict[str, float]:
        """The step seconds and counts, with the totals they tile."""
        return {f: getattr(self, f) for f in
                ("fetch_s", "gather_s", "model_s") + self.SECONDS
                + self.COUNTS}

    def merge(self, other: "TierStats") -> "TierStats":
        """Aggregate (for the multi-table facade)."""
        for f in ("batches", "lookups", "hits", "misses", "prefetch_hits",
                  "on_demand_rows", "evictions") + self.COUNTS:
            setattr(self, f, getattr(self, f) + getattr(other, f))
        for f in ("fetch_s", "gather_s", "model_s",
                  "modeled_fetch_s") + self.SECONDS:
            setattr(self, f, getattr(self, f) + getattr(other, f))
        return self

    def publish(self, reg, prefix: str = "store"):
        """Publish into a :class:`repro.obs.MetricsRegistry` under the
        ``store.*`` namespace (see docs/architecture.md)."""
        for key, val in (
            ("batches", self.batches), ("lookups", self.lookups),
            ("fast.hits", self.hits), ("fast.misses", self.misses),
            ("fast.prefetch_hits", self.prefetch_hits),
            ("fast.on_demand_rows", self.on_demand_rows),
            ("fast.evictions", self.evictions),
            ("time.fetch_s", self.fetch_s),
            ("time.gather_s", self.gather_s),
            ("time.model_s", self.model_s),
            ("time.modeled_fetch_s", self.modeled_fetch_s),
            *((f"time.{f}", getattr(self, f)) for f in self.SECONDS),
            *((f"steps.{f}", getattr(self, f)) for f in self.COUNTS),
        ):
            reg.counter(f"{prefix}.{key}").inc(val)
        reg.gauge(f"{prefix}.fast.hit_rate").set(self.hit_rate)
        return reg


class TieredEmbeddingStore:
    """Host table (N, D) + device buffer (C, D) with pluggable policy."""

    def __init__(self, host_table: np.ndarray, capacity: int,
                 policy: str = "lru", eviction_speed: int = 4,
                 fetch_us_per_row: float = 10.0, fetch_us_fixed: float = 30.0,
                 quantize: bool = False, row_format: Optional[str] = None,
                 use_kernel: Optional[bool] = None,
                 kernel_interpret: bool = False,
                 warmup_batch: Optional[int] = None):
        """``quantize=True``: quantized rows + per-row fp32 scale in the
        fast tier — the mixed-precision-embedding trick the paper cites
        ([90]): ``D + 4`` bytes per resident row instead of ``D *
        itemsize``, so at a fixed byte budget the buffer holds ~2-4x the
        rows and the hit rate rises (gated fixed-byte-budget cells in
        benchmarks/bench_e2e.py).  ``row_format`` picks the storage format
        (``"int8"`` default, or ``"fp8"`` = float8_e4m3fn); passing it
        without ``quantize=True`` is an error.

        ``use_kernel``: route the device gather (and, under quantize, the
        admit-side quantizer) through the Pallas kernels.  Default auto:
        :func:`kernel_gather_ok` — a TPU, D % 128 == 0 and 32-bit fast-tier
        rows.  An *explicit* ``use_kernel=True`` is validated, never
        silently downgraded: off the TPU backend it needs
        ``kernel_interpret=True`` (the Pallas interpreter — the CPU test
        lane), and on the compiled path D must be a multiple of 128 and
        the rows 32-bit (so not ``quantize=True``).

        ``warmup_batch``: eagerly compile the jitted scatter/gather for
        every power-of-two shape bucket a batch of up to this many ids can
        hit (see :meth:`warmup`); None skips the warmup."""
        self.host = host_table
        n, d = host_table.shape
        self.capacity = max(1, int(capacity))  # same clamp as RecMGBuffer
        self.quantize = quantize
        if row_format is not None and not quantize:
            raise ValueError("row_format requires quantize=True "
                             "(fp32 rows have no storage format knob)")
        self.row_format = row_format or "int8"
        if self.row_format not in _QDTYPE:
            raise ValueError(f"unknown row_format {self.row_format!r} "
                             f"(expected one of {sorted(_QDTYPE)})")
        if quantize:
            self.buffer = jnp.zeros((self.capacity, d),
                                    _QDTYPE[self.row_format])
            self.scales = jnp.zeros((self.capacity,), jnp.float32)
        else:
            self.buffer = jnp.zeros((self.capacity, d), host_table.dtype)
        # -------- array-backed residency state (see module docstring) -----
        self._slot_map = np.full(n, -1, np.int32)
        self._slot_key = np.full(self.capacity, -1, np.int64)
        self._free = np.arange(self.capacity - 1, -1, -1, dtype=np.int32)
        self._n_free = self.capacity
        self._last_use = np.zeros(self.capacity, np.int64)
        self._admit_seq = np.zeros(self.capacity, np.int64)
        self._pf_flag = np.zeros(self.capacity, bool)
        self._clock = 1
        self.policy = policy
        # The store owns RESIDENCY (_slot_map); the RecMG structure only
        # ranks priorities, so it gets unbounded capacity and never
        # self-evicts — under recmg its live set mirrors the resident set
        # exactly (checked in check_invariants), which is what lets
        # ``_admit`` rank a whole victim batch in one engine pass.
        self.recmg = RecMGBuffer(1 << 40, eviction_speed, n_keys_hint=n)
        self.fetch_us_per_row = fetch_us_per_row
        self.fetch_us_fixed = fetch_us_fixed
        self.stats = TierStats()
        self._staged: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        self.kernel_interpret = bool(kernel_interpret)
        backend = jax.default_backend()
        fast_dtype = self.buffer.dtype
        if use_kernel is None:
            # Auto mode may downgrade: the kernel path only engages when
            # the backend can actually compile it for this table shape.
            use_kernel = kernel_gather_ok(backend, d, fast_dtype)
        elif use_kernel and not self.kernel_interpret:
            # An explicit request is a contract — validate, never
            # silently drop (the old ``and not quantize`` downgrade hid
            # exactly this class of misconfiguration).
            if backend != "tpu":
                raise ValueError(
                    "use_kernel=True requires the TPU backend; pass "
                    "kernel_interpret=True to run the Pallas kernels in "
                    "interpret mode (the CPU test lane)")
            if d % 128:
                raise ValueError(
                    f"use_kernel=True requires D % 128 == 0 (got D={d}): "
                    "the compiled kernels stream rows through the 128-lane "
                    "layout — pad the table or pass kernel_interpret=True")
            if not kernel_gather_ok(backend, d, fast_dtype):
                raise ValueError(
                    f"use_kernel=True on the TPU requires 32-bit fast-tier "
                    f"rows (got {np.dtype(fast_dtype)}): a one-row DMA of "
                    "quantized rows does not compile, so quantized stores "
                    "take the XLA gather there")
        self.use_kernel = bool(use_kernel)
        if self.use_kernel:
            self._gather_inv, self._gather_ov = _kernel_gathers(
                quantized=quantize, interpret=self.kernel_interpret)
        elif quantize:
            self._gather_inv, self._gather_ov = _JIT_GATHER_Q, _JIT_GATHER_Q_OV
        else:
            self._gather_inv, self._gather_ov = _JIT_GATHER, _JIT_GATHER_OV
        self._out_np_dtype = np.dtype(
            np.float32 if quantize else self.buffer.dtype)
        if quantize:
            if self.use_kernel:
                self._scatter_q = _kernel_scatter_q(
                    self.row_format, interpret=self.kernel_interpret)
            else:
                rf = self.row_format
                self._scatter_q = lambda buf, sc, idx, rows: \
                    _JIT_STORE_WRITE_Q(buf, sc, idx, rows, rf)
        if warmup_batch:
            self.warmup(warmup_batch)

    # ---------------- compat / introspection ----------------

    @property
    def slot_of(self) -> Dict[int, int]:
        """Dict view of key -> slot residency (seed-compatible read API)."""
        res = np.flatnonzero(self._slot_key >= 0)
        return {int(self._slot_key[s]): int(s) for s in res}

    @property
    def n_resident(self) -> int:
        return self.capacity - self._n_free

    def resident_mask(self, ids: np.ndarray) -> np.ndarray:
        """Vectorized residency probe: True where ``ids`` are in the fast
        tier right now (public API for the serving runtime's cancel-
        before-issue and for tests; does not touch recency state)."""
        return self._slot_map[np.asarray(ids, np.int64).ravel()] >= 0

    def lookup_resident(self, ids: np.ndarray):
        """Degraded read for over-deadline requests: ``(rows, n_default)``
        where resident ids get their current (possibly stale) fast-tier
        row and slow-tier misses get a zero default row — never a wrong
        shape, never a slow-tier fetch.  Pure read: no recency update, no
        admission/eviction, no stats mutation, so the main accounting
        identities are untouched."""
        ids = np.asarray(ids, np.int64).ravel()
        out = np.zeros((ids.size, self.host.shape[1]), self._out_np_dtype)
        slots = self._slot_map[ids]
        res = slots >= 0
        n_res = int(np.count_nonzero(res))
        if n_res:
            s = slots[res].astype(np.int64)
            rows = np.asarray(self.buffer)[s]
            if self.quantize:
                rows = rows.astype(np.float32) \
                    * np.asarray(self.scales)[s][:, None]
            out[res] = rows.astype(self._out_np_dtype, copy=False)
        return out, int(ids.size) - n_res

    def check_invariants(self):
        """Residency invariants (used by tests): the slot map and slot->key
        array are exact inverses, the free stack covers the rest, and under
        recmg the priority engine's live set mirrors residency exactly."""
        res = np.flatnonzero(self._slot_key >= 0)
        keys = self._slot_key[res]
        assert np.array_equal(self._slot_map[keys], res.astype(np.int32))
        assert len(res) == self.capacity - self._n_free
        assert np.count_nonzero(self._slot_map >= 0) == len(res)
        free = self._free[: self._n_free]
        assert np.all(self._slot_key[free] < 0)
        if self.policy == "recmg":
            # Every resident key holds a live ranking entry; the engine may
            # additionally hold *stale* entries for non-resident keys
            # (prefetch rankings that outlived their row — the seed's heap
            # had the same, drained lazily during victim selection).
            eng = self.recmg.engine
            live = eng.live_keys()
            assert eng.count == live.size
            assert np.all(np.isin(keys, live))

    def warmup(self, batch_hint: int):
        """Eagerly compile the jitted scatter/gather for every power-of-two
        shape bucket a batch of up to ``batch_hint`` ids can hit, so XLA
        compiles land at construction instead of inside measured batches
        (they showed up as ~600ms p99 spikes against a ~10ms p50).  The
        jitted functions are module-level: across tables/shards only the
        first store pays each compile."""
        bi = _bucket(int(batch_hint))
        d = self.host.shape[1]
        b = 16
        while b <= bi:
            iv = jnp.zeros((2, b), jnp.int32)
            ov = jnp.zeros(b, bool)
            hr = jnp.zeros((b, d), self._out_np_dtype)
            gather_args = (
                (self.buffer, self.scales) if self.quantize
                else (self.buffer,)
            )
            self._gather_inv(*gather_args, iv)
            self._gather_ov(*gather_args, iv, ov, hr)
            # Scatter warm-up must not clobber buffer contents: rewrite
            # slot 0 with its own current row (a no-op write).
            slots = jnp.zeros(b, jnp.int32)
            if self.quantize:
                # Warm the fused quantize+scatter with slot 0's own
                # dequantized row: re-quantizing a quantized row is
                # value-preserving (same scale derivation, round-half-even
                # maps each code back to itself), so resident contents
                # survive to within the format's quantization error.
                r0 = (np.asarray(self.buffer[0:1]).astype(np.float32)
                      * float(np.asarray(self.scales[0])))
                rows = jnp.asarray(np.repeat(r0, b, axis=0))
                self.buffer, self.scales = self._scatter_q(
                    self.buffer, self.scales, slots, rows)
            else:
                r0 = np.repeat(np.asarray(self.buffer[0:1]), b, axis=0)
                self.buffer = _JIT_STORE_WRITE(self.buffer, slots,
                                               jnp.asarray(r0))
            b <<= 1
        jax.block_until_ready(self.buffer)

    def gather_program_text(self, n_ids: int) -> str:
        """Compiled HLO text of this store's lookup gather for a batch of
        ``n_ids`` ids (its shape bucket): the program every such batch
        runs.  A ``tpu_custom_call`` in it marks the Pallas kernel path."""
        iv = jnp.zeros((2, _bucket(n_ids)), jnp.int32)
        args = (self.buffer, self.scales) if self.quantize else (self.buffer,)
        return self._gather_inv.lower(*args, iv).compile().as_text()

    # ---------------- slot allocation / eviction ----------------

    def _alloc(self, m: int) -> np.ndarray:
        slots = self._free[self._n_free - m: self._n_free][::-1].copy()
        self._n_free -= m
        return slots

    def _release(self, slots: np.ndarray):
        k = len(slots)
        self._free[self._n_free: self._n_free + k] = slots[::-1]
        self._n_free += k

    def _evict_slots(self, victim_slots: np.ndarray):
        """Batched eviction: clear residency + prefetch flags, free slots."""
        vk = self._slot_key[victim_slots]
        self._slot_map[vk] = -1
        self._slot_key[victim_slots] = -1
        self._pf_flag[victim_slots] = False
        self.stats.evictions += len(victim_slots)
        self._release(np.asarray(victim_slots, np.int32))

    def _pick_victim_recmg(self) -> int:
        victim = self.recmg.populate()
        while victim is not None and self._slot_map[victim] < 0:
            victim = self.recmg.populate()  # stale non-resident entry
        if victim is None:  # priorities exhausted: oldest-admitted resident
            res = np.flatnonzero(self._slot_key >= 0)
            victim = int(self._slot_key[res[np.argmin(self._admit_seq[res])]])
        return victim

    def _bind(self, keys: np.ndarray, slots: np.ndarray):
        """Point keys at slots and stamp admission order / recency."""
        m = len(keys)
        self._slot_map[keys] = slots
        self._slot_key[slots] = keys
        self._admit_seq[slots] = self._clock + np.arange(m)
        self._last_use[slots] = self._clock + np.arange(m)
        self._clock += m

    def _admit(self, missing: np.ndarray) -> np.ndarray:
        """Assign slots for all missing keys at once, evicting as needed.

        Returns a bool mask over ``missing``: True where the key is resident
        after the batch (False = overflow: the working set exceeded the
        buffer, so the row is served straight from the host tier).
        """
        m = len(missing)
        kept = np.ones(m, bool)
        if self.policy == "recmg":
            if m <= self._n_free:
                slots = self._alloc(m)
                self._bind(missing, slots)
                self.recmg.set_priorities(missing, self.recmg.ev,
                                          only_new=True)
            elif self.recmg.engine.contains_many(missing).any():
                # Resurrection: a missing key still holds a stale ranking
                # entry (it was prefetch-ranked after being evicted in its
                # own admission batch).  Re-admitting it must *keep* that
                # old entry (the seed's only_new semantics), and the old
                # entry can even be chosen as a victim mid-batch — exact
                # only in the per-key oracle.  Rare: requires a stale key
                # to be demand-missed while its entry survives.
                self._admit_recmg_sequential(missing, kept)
            else:
                self._admit_recmg_batched(missing, kept)
            return kept
        # ---- LRU: fully batched ----
        if m >= self.capacity:
            # Every old resident gets evicted, then the first m-C missing
            # keys are themselves evicted by later ones in admit order:
            # only the last C keys of the (sorted-unique) batch survive.
            old = np.flatnonzero(self._slot_key >= 0)
            if len(old):
                self._evict_slots(old)
            kept[: m - self.capacity] = False
            # The seed admitted those m-C keys and then evicted each one;
            # count them so the eviction stat matches the reference.
            self.stats.evictions += m - self.capacity
            new = missing[m - self.capacity:]
            self._bind(new, self._alloc(self.capacity))
            return kept
        need = m - self._n_free
        if need > 0:
            res = np.flatnonzero(self._slot_key >= 0)
            if need >= len(res):
                victims = res
            else:  # rank all victims in one pass
                victims = res[np.argpartition(self._last_use[res],
                                              need - 1)[:need]]
            self._evict_slots(victims)
        self._bind(missing, self._alloc(m))
        return kept

    def _admit_recmg_batched(self, missing: np.ndarray, kept: np.ndarray):
        """Fully batched recmg admission under eviction pressure: the
        engine ranks all victims in one vectorized pass
        (:meth:`~repro.core.priority_engine.ArrayPriorityEngine.
        admit_interleaved`), resolving own-batch evictions (a key of this
        batch evicted by a later one) vectorially.  Counter- and
        victim-identical to :meth:`_admit_recmg_sequential` (the property
        suite fuzzes both against the seed reference)."""
        m = len(missing)
        slot_map = self._slot_map
        victims, own, kept_eng = self.recmg.engine.admit_interleaved(
            missing, self.recmg.ev, self._n_free,
            resident_fn=lambda kk: slot_map[kk] >= 0)
        ext = victims[~own]
        if ext.size:
            vs = self._slot_map[ext]
            self._slot_map[ext] = -1
            self._slot_key[vs] = -1
            self._pf_flag[vs] = False
            self._release(vs.astype(np.int32, copy=False))
        # Own-batch victims were bound and then evicted by the sequential
        # loop; both count as evictions and both consumed a clock tick.
        self.stats.evictions += int(victims.size)
        kidx = np.flatnonzero(kept_eng)
        kk = missing[kidx]
        slots = self._alloc(kidx.size)
        self._slot_map[kk] = slots
        self._slot_key[slots] = kk
        self._admit_seq[slots] = self._clock + kidx
        self._last_use[slots] = self._clock + kidx
        self._clock += m
        kept[:] = kept_eng

    def _admit_recmg_sequential(self, missing: np.ndarray, kept: np.ndarray):
        """Seed-faithful per-key admission under recmg eviction pressure
        (the equivalence oracle for :meth:`_admit_recmg_batched`)."""
        slot_map, slot_key = self._slot_map, self._slot_key
        pos = {int(k): i for i, k in enumerate(missing.tolist())}
        for i, k in enumerate(missing.tolist()):
            if self._n_free == 0:
                v = self._pick_victim_recmg()
                vs = slot_map[v]
                slot_map[v] = -1
                slot_key[vs] = -1
                self._pf_flag[vs] = False
                self.stats.evictions += 1
                self._release(np.asarray([vs], np.int32))
                j = pos.get(v)
                if j is not None and j < i:
                    kept[j] = False  # own-batch key evicted mid-batch
            slot = int(self._alloc(1)[0])
            slot_map[k] = slot
            slot_key[slot] = k
            self._admit_seq[slot] = self._clock
            self._last_use[slot] = self._clock
            self._clock += 1
            if not self.recmg.contains(k):
                self.recmg.set_priority(k, self.recmg.ev)

    # ---------------- main path ----------------

    def lookup(self, ids: np.ndarray) -> jnp.ndarray:
        """ids: (M,) int64 -> (M, D) embeddings from the fast tier,
        fetching misses on demand.  One vectorized pass: hit/miss partition
        via the slot map, batched admission, single fused scatter + gather.
        The result stays on the device (feed it straight into the jitted
        forward); facades that merge sub-results host-side should use
        :meth:`lookup_host` instead, which saves the device-side slice.
        """
        return self._lookup(ids, to_host=False)

    def lookup_host(self, ids: np.ndarray) -> np.ndarray:
        """:meth:`lookup` materialized as a NumPy array in one transfer —
        the multi-table and sharded facades reassemble per-store results
        on the host, so slicing there is free.  Counters are identical to
        :meth:`lookup`."""
        return self._lookup(ids, to_host=True)

    def _lookup(self, ids: np.ndarray, to_host: bool):
        """Shared lookup pipeline, timed as consecutive steps under the
        ``store.lookup`` span (one clock reading per boundary,
        :class:`~repro.obs.tracing.Steps`): ``partition`` | ``admit`` =
        ``slow_read`` + ``residency`` + ``write`` (= ``fetch_s``) |
        ``partition`` again for the post-admission slot map and the LRU
        touch | ``gather`` (dispatch) +
        ``sync`` (= ``gather_s``)."""
        self._drain_staged()
        st = self.stats
        steps = Steps(get_tracer())
        with steps.group("store", "lookup", track="store") as lookup_span:
            out, m_ids, t_gather = self._lookup_padded(ids, steps,
                                                       lookup_span)
            with steps.step(st, "sync_s", "store", "sync", track="store"):
                if to_host:
                    out = np.asarray(out)[:m_ids]
                else:
                    out = out[:m_ids]
                    jax.block_until_ready(out)
        st.gather_s += steps.t - t_gather
        return out

    def _lookup_padded(self, ids: np.ndarray, steps: Steps, lookup_span):
        """The lookup's steps up to the gather's dispatch; returns (padded
        device rows, true batch size, the reading the gather started at)
        and sets the lookup span's args."""
        st = self.stats
        ev0 = st.evictions
        with steps.step(st, "partition_s", "store", "partition",
                        track="store"):
            ids = np.asarray(ids).ravel()
            st.batches += 1
            st.lookups += ids.size
            uniq, inv = np.unique(ids, return_inverse=True)
            slots_u = self._slot_map[uniq]
            miss_mask = slots_u < 0
            n_hit = int(np.count_nonzero(~miss_mask[inv]))
            st.hits += n_hit
            st.misses += int(ids.size) - n_hit
            hit_slots = slots_u[~miss_mask]
            pf = self._pf_flag[hit_slots]
            n_pf = int(np.count_nonzero(pf))
            if n_pf:  # first-touch prefetch attribution
                st.prefetch_hits += n_pf
                self._pf_flag[hit_slots] = False
            missing = uniq[miss_mask]

        if missing.size:
            t_admit = steps.t
            with steps.group("store", "admit", track="store",
                             miss_rows=int(missing.size)):
                with steps.step(st, "slow_read_s", "store", "slow_read",
                                track="store"):
                    rows = self.host[missing]
                with steps.step(st, "residency_s", "store", "residency",
                                track="store"):
                    kept = self._admit(missing)
                with steps.step(st, "write_s", "store", "write",
                                track="store"):
                    wkeys = missing[kept]
                    self._write_rows(self._slot_map[wkeys], rows[kept])
                    # No sync here: the scatter pipelines into the
                    # gather below and both resolve in that single
                    # device sync (fetch_s is the host-side admit +
                    # dispatch time; execution lands in gather_s).
            st.fetch_s += steps.t - t_admit

        if missing.size or self.policy == "lru":
            with steps.step(st, "partition_s", "store", "partition",
                            track="store"):
                if missing.size:
                    st.on_demand_rows += int(missing.size)
                    st.modeled_fetch_s += (
                        self.fetch_us_fixed
                        + self.fetch_us_per_row * missing.size) * 1e-6
                    slots_u = self._slot_map[uniq]  # post-admission
                if self.policy == "lru":
                    # Batched touch: every resident key of this batch
                    # moves to the MRU end, ordered by sorted-unique
                    # position (seed order).
                    res = slots_u >= 0
                    rs = slots_u[res]
                    self._last_use[rs] = self._clock + np.flatnonzero(res)
                    self._clock += uniq.size

        u = uniq.size
        m_ids = ids.size
        t_gather = steps.t
        with steps.step(st, "gather_dispatch_s", "store", "gather",
                        track="store", uniq=int(u)):
            out = self._gather(uniq, slots_u, inv, m_ids)
        # Span args carry the batch's exact counter deltas — the trace
        # <-> metrics reconciliation sums these over all lookup spans.
        lookup_span.set(ids=m_ids, uniq=int(u), hit_ids=n_hit,
                        miss_ids=m_ids - n_hit,
                        miss_rows=int(missing.size),
                        evictions=st.evictions - ev0)
        return out, m_ids, t_gather

    def _gather(self, uniq: np.ndarray, slots_u: np.ndarray,
                inv: np.ndarray, m_ids: int):
        """Dispatch the batch's device gather; returns the padded rows.

        One fused jitted pass does the slot gather, the overflow
        where-select, and the unique->request expansion, so the result
        never bounces through the host.  The two index vectors are packed
        into one (2, bucket) operand — a single transfer — and share ONE
        power-of-two bucket (u <= M always): independent buckets would
        give O(log^2) compiled shape combos, and per-table sub-batch sizes
        vary enough to hit them all at runtime.  Buckets are warmed
        eagerly by :meth:`warmup`."""
        gather_args = (
            (self.buffer, self.scales) if self.quantize else (self.buffer,)
        )
        u = uniq.size
        bsz = _bucket(m_ids)
        iv = np.zeros((2, bsz), np.int32)
        np.maximum(slots_u, 0, out=iv[0, :u], casting="unsafe")
        iv[1, :m_ids] = inv
        overflow = slots_u < 0
        n_over = int(np.count_nonzero(overflow))
        if not n_over:
            self.stats.h2d_bytes += iv.nbytes
            return self._gather_inv(*gather_args, jnp.asarray(iv))
        # A batch whose unique working set exceeds the buffer can evict
        # rows admitted earlier in the same batch; stage those rows from
        # the host tier into the padded gather input and fold them in with
        # a jitted where-select (counted as on-demand already).
        ov = np.zeros(bsz, bool)
        ov[:u] = overflow
        hrows = np.zeros((bsz, self.host.shape[1]), self._out_np_dtype)
        hrows[:u][overflow] = self.host[uniq[overflow]]
        self.stats.overflow_rows += n_over
        self.stats.h2d_bytes += iv.nbytes + ov.nbytes + hrows.nbytes
        return self._gather_ov(*gather_args, jnp.asarray(iv),
                               jnp.asarray(ov), jnp.asarray(hrows))

    def _write_rows(self, slots: np.ndarray, rows: np.ndarray):
        if not len(slots):
            return
        self.stats.write_rows += len(slots)
        # Bucket-pad the scatter like the gather: repeat the last
        # (slot, row) pair — rewriting one slot with its own row is a
        # no-op, and the fixed shapes keep XLA from recompiling per batch.
        pad = _bucket(len(slots)) - len(slots)
        if pad:
            slots = np.concatenate((slots, np.repeat(slots[-1:], pad)))
            rows = np.concatenate((rows, np.repeat(rows[-1:], pad, axis=0)))
        if self.quantize:
            # Device-side quantize + scatter in one fused dispatch (Pallas
            # quantizer on the kernel path, jnp reference otherwise): no
            # host NumPy pass, and the write pipelines into the batch's
            # gather exactly like the fp32 scatter does.
            rows = np.asarray(rows, np.float32)
            self.buffer, self.scales = self._scatter_q(
                self.buffer, self.scales, jnp.asarray(slots),
                jnp.asarray(rows))
        else:
            self.buffer = _JIT_STORE_WRITE(
                self.buffer, jnp.asarray(slots), jnp.asarray(rows))
        self.stats.h2d_bytes += slots.nbytes + rows.nbytes

    # ---------------- RecMG co-management hooks ----------------

    def stage_model_outputs(self, trunk: np.ndarray, bits: np.ndarray,
                            prefetch_ids: np.ndarray):
        """Double-buffered Algorithm 1: queue the model outputs now, apply
        them at the next batch boundary, so the producer never blocks an
        in-flight lookup.  Serving loops should call :meth:`flush_staged`
        in the gap between batches (off the latency-measured path); the
        next ``lookup`` drains any remainder as a fallback."""
        self._staged.append((np.asarray(trunk), np.asarray(bits),
                             np.asarray(prefetch_ids)))

    def flush_staged(self):
        """Apply all staged model outputs now (the inter-batch gap)."""
        self._drain_staged()

    def _drain_staged(self):
        if self._staged:
            staged, self._staged = self._staged, []
            self._populate(staged)

    def apply_model_outputs(self, trunk: np.ndarray, bits: np.ndarray,
                            prefetch_ids: np.ndarray):
        """Algorithm 1, invoked between batches (pipelined)."""
        self._populate([(trunk, bits, prefetch_ids)])

    def _populate(self, items):
        """Apply ``(trunk, bits, prefetch_ids)`` items (1-D arrays) in
        order, under ONE ``store.populate`` span whose args sum theirs.

        Under recmg the trunks of each maximal run of items that carry no
        prefetch ids are ranked in one engine pass: the ranking buffer is
        unbounded, so nothing evicts; the epoch and the slot map hold still
        until a prefetch is admitted; and ``set_many`` gives every
        occurrence its own seq, the last winning, as a per-item loop does.
        An item with prefetch ids ends the run its trunk joins, so a later
        trunk is masked against the slot map after that admission."""
        st = self.stats
        ev0, passes0 = st.evictions, st.rank_passes
        recmg = self.policy == "recmg"
        with get_tracer().span("store", "populate", track="store") as span:
            n_trunk = n_pf = 0
            keys, bits = [], []
            for trunk, b, pf in items:
                m = len(trunk)
                if m != len(b):  # zip semantics: the shorter side wins
                    m = min(m, len(b))
                    trunk, b = trunk[:m], b[:m]
                n_trunk += m
                if recmg and m:
                    keys.append(trunk)
                    bits.append(b)
                if len(pf):
                    if keys:
                        self._rank(keys, bits)
                        keys, bits = [], []
                    n_pf += self._prefetch(pf)
            if keys:
                self._rank(keys, bits)
            st.populate_calls += len(items)
            span.set(trunk=n_trunk, pf_rows=n_pf, calls=len(items),
                     rank_passes=st.rank_passes - passes0,
                     evictions=st.evictions - ev0)

    def _rank(self, keys: List[np.ndarray], bits: List[np.ndarray]):
        """One engine pass over a run's concatenated trunks and bits."""
        st = self.stats
        t0 = time.perf_counter()
        trunk = np.concatenate(keys).astype(np.int64, copy=False)
        b = np.concatenate(bits)
        # Only rank RESIDENT keys (pipelined outputs can reference
        # vectors already evicted; ranking them would desync
        # priorities/residency).
        res = self._slot_map[trunk] >= 0
        self.recmg.load_embeddings(trunk[res], b[res], [])
        dt = time.perf_counter() - t0
        st.rank_passes += 1
        st.rank_s += dt
        st.model_s += dt

    def _prefetch(self, prefetch_ids) -> int:
        """Admit an item's non-resident prefetch targets (and, under recmg,
        rank them); returns the rows prefetched."""
        st = self.stats
        t0 = time.perf_counter()
        pf = self._new_prefetch_keys(
            np.asarray(prefetch_ids, np.int64).ravel())
        if pf.size:
            self._fetch_prefetch(pf)
        if self.policy == "recmg":
            self.recmg.set_priorities(pf, self.recmg.ev)
            dt = time.perf_counter() - t0
            st.prefetch_s += dt
            st.model_s += dt
        return pf.size

    def _new_prefetch_keys(self, pf_ids: np.ndarray) -> np.ndarray:
        """Non-resident prefetch targets, deduplicated, first-occurrence
        order preserved (the seed admitted duplicates twice, leaking a
        buffer slot per duplicate; the batched engine dedupes)."""
        if not pf_ids.size:
            return pf_ids
        pf = pf_ids[self._slot_map[pf_ids] < 0]
        if pf.size > 1:
            _, first = np.unique(pf, return_index=True)
            pf = pf[np.sort(first)]
        return pf

    def _fetch_prefetch(self, keys: np.ndarray):
        rows = self.host[keys]
        kept = self._admit(keys)
        wkeys = keys[kept]
        slots = self._slot_map[wkeys]
        self._write_rows(slots, rows[kept])
        self._pf_flag[slots] = True

    def modeled_batch_ms(self) -> float:
        """Analytic per-batch latency contribution of the slow tier."""
        return 1e3 * self.stats.modeled_fetch_s / max(self.stats.batches, 1)

    def publish_metrics(self, reg):
        """Publish this store's counters under ``store.*`` (uniform
        facade/store surface for the serving entry points)."""
        return self.stats.publish(reg, prefix="store")
