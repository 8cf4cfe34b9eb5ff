"""Activation cache for Parallel Adapters (paper §IV-B, §V-B) — v2.

Because the backbone is frozen, the taps ``b_0..b_L`` and the final
hidden state ``b_final`` are invariant per input sequence. During epoch 1
the cache captures them; from epoch 2 on the backbone forward is skipped
entirely and the adapter trains straight from the cache (pure data
parallelism — paper Fig. 11).

v2 extends the byte-budgeted RAM/disk store of v1 with the three pieces
that turn it from a demo into the deployable subsystem the paper costs
out in §V-B:

* **Compressed entries** — a ``compress=`` policy (``"f32"``, ``"bf16"``,
  ``"int8"``) applied at ``put`` time. ``bf16`` halves storage with a
  ≤2⁻⁸ relative error; ``int8`` is the same block-wise absmax scheme the
  backbone weights use (:mod:`repro.core.quantization`, paper §IV-D /
  QLoRA), ~3.9× smaller than f32 including scales. The byte budget and
  all eviction/spill accounting operate on *compressed* bytes.
* **Async prefetch** — :class:`CachePrefetcher` runs a background thread
  over the epoch's known batch order (``DataPipeline.epoch_order``),
  decompressing/loading the *next* batches while the current train step
  runs, with the host→device transfer started early (double-buffered via
  a bounded queue).
* **Compressed handoff** — ``get``/``get_batch``/``CachePrefetcher``
  accept ``compressed=True`` and hand entries to the training step in
  their *storage* form (int8 payload + scales as ``{"q", "scale"}``
  dicts, bf16 arrays) instead of eagerly decompressing:
  ``repro.kernels.cached_step`` then dequantises tile-wise in VMEM, so
  the host→device transfer and HBM reads stay at storage width
  (``--kernels pallas``).
* **Cross-run persistence** — ``save_manifest``/``open_persistent``
  record and validate a manifest (corpus + backbone fingerprints,
  compression policy) next to the spill files, so a re-run against the
  same ``--cache-dir`` starts with a warm cache and performs **zero**
  backbone forwards. A mismatching manifest invalidates loudly and
  discards the stale entries.

Storage cost is ``(n_periods + 2) · S · d`` values per sequence with
``b_final`` folded in (the paper's ``s × h × l`` analysis, +1 for the
final hidden state). Spills are ``.npz`` shards (the paper reloads per
micro-batch from embedded flash); each archive handle is closed after
the read.
"""

from __future__ import annotations

import json
import os
import queue
import sys
import threading
from dataclasses import dataclass, field
from typing import Dict, Iterable, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.quantization import QTensor, dequantize, quantize

COMPRESS_POLICIES = ("f32", "bf16", "int8")
_INT8_BLOCK = 128
MANIFEST_NAME = "manifest.json"
MANIFEST_VERSION = 2


def cache_bytes_per_sequence(
    cfg, seq_len: int, dtype_bytes: float = 4, with_final: bool = False
) -> int:
    """Paper §V-B storage analysis: s·h·(l+1) values per sequence.

    ``with_final=True`` adds the ``b_final`` plane that v2 entries fold
    in (s·h·(l+2)) — what ``--cache-budget-mb`` sizing should use; pass
    ``policy_bytes_per_value(policy)`` as ``dtype_bytes`` for compressed
    entries."""
    planes = cfg.n_periods + (2 if with_final else 1)
    return int(planes * seq_len * cfg.d_model * dtype_bytes)


def policy_bytes_per_value(policy: str, block: int = _INT8_BLOCK) -> float:
    """Stored bytes per cached value under each compression policy
    (int8 includes the per-block f32 scale amortised over the block)."""
    return {"f32": 4.0, "bf16": 2.0, "int8": 1.0 + 4.0 / block}[policy]


# ---------------------------------------------------------------------------
# Compressed tensors / cache entries
# ---------------------------------------------------------------------------


@dataclass
class _CTensor:
    """One compressed host tensor + enough metadata to invert it.

    f32:  data float32, scale None
    bf16: data ml_dtypes.bfloat16 (stored as uint16 inside npz shards)
    int8: data int8 payload, scale f32 per-block absmax/127
          (exactly ``quantization.quantize(bits=8, block=_INT8_BLOCK)``)
    """

    policy: str
    data: np.ndarray
    scale: Optional[np.ndarray]
    orig_last: int
    block: int = 0

    @property
    def nbytes(self) -> int:
        return self.data.nbytes + (0 if self.scale is None else self.scale.nbytes)


def _storage_form(x, policy: str, orig_last: Optional[int] = None):
    """``(part, orig_last)``: ``x`` in the form the fetch copies to the
    host, where it stays an array of whatever kind it came as. An int8
    ``{"q", "scale"}`` dict, as emitted at the tap site by the pallas
    OpSet (``emit_tap``), is adopted as-is (no recompress, no f32
    round-trip) provided the policy is int8; ``orig_last`` then names the
    unpadded feature width. Other int8 input is quantized here, with jnp
    (on the device for a device array). f32/bf16 parts are cast on the
    host after the fetch (:func:`_host_ct`)."""
    if isinstance(x, dict):
        if policy != "int8":
            raise ValueError(
                f"storage-form (q/scale) tap requires the int8 policy, got {policy!r}"
            )
        return x, x["q"].shape[-1] if orig_last is None else orig_last
    if policy == "int8":
        qt = quantize(jnp.asarray(x, jnp.float32), bits=8, block=_INT8_BLOCK)
        return {"q": qt.q, "scale": qt.scale}, qt.orig_last
    if policy in ("f32", "bf16"):
        return x, x.shape[-1]
    raise ValueError(f"compress must be one of {COMPRESS_POLICIES}, got {policy!r}")


def _host_ct(part, policy: str, orig_last: int) -> _CTensor:
    """The host tensor of a :func:`_storage_form` part: int8 dicts as
    fetched (the block follows from the shapes), f32/bf16 arrays cast to
    the policy's dtype."""
    if isinstance(part, dict):
        q, scale = np.asarray(part["q"]), np.asarray(part["scale"])
        return _CTensor("int8", q, scale, orig_last, q.shape[-1] // scale.shape[-1])
    target = np.float32 if policy == "f32" else ml_dtypes.bfloat16
    return _CTensor(policy, np.asarray(part, target), None, orig_last)


def _compress(x, policy: str, own: bool = False, orig_last: Optional[int] = None) -> _CTensor:
    """``own=True`` guarantees the payload owns its buffer: a same-dtype
    conversion is a no-copy view, and an entry holding a view of e.g. one
    row of a (B,S,d) batch array would pin the whole batch in RAM — the
    byte budget would no longer bound real memory."""
    part, last = _storage_form(x, policy, orig_last)
    if not isinstance(part, dict):
        part = np.asarray(part)
    ct = _host_ct(part, policy, last)
    if own and ct.scale is None and (ct.data is part or ct.data.base is not None):
        ct.data = ct.data.copy()
    return ct


def _ct_index(ct: _CTensor, idx) -> _CTensor:
    """Slice one sequence out of a batch-compressed tensor. Copies, so the
    per-sequence entry owns its bytes instead of pinning the batch array.
    Valid because compression is independent along the last axis (blocks
    never straddle the sliced leading axes)."""
    return _CTensor(
        ct.policy,
        ct.data[idx].copy(),
        None if ct.scale is None else ct.scale[idx].copy(),
        ct.orig_last,
        ct.block,
    )


def _decompress(ct: _CTensor, dtype=np.float32) -> np.ndarray:
    """dtype=None returns the storage dtype where it is a real float type
    (bf16 entries ship compressed to the device; the train step upcasts).

    int8 entries dequantize on the host to f32 here — their H2D transfer
    is full-width. To keep the transfer at integer width instead, read
    with ``compressed=True`` (:meth:`ActivationCache.get_batch`): the
    raw ``{"q", "scale"}`` payload then reaches the jitted step and
    `repro.kernels.cached_step` dequantizes it in VMEM."""
    if ct.policy in ("f32", "bf16"):
        return ct.data if dtype is None else np.asarray(ct.data, dtype)
    qt = QTensor(jnp.asarray(ct.data), jnp.asarray(ct.scale), 8, ct.block, ct.orig_last)
    out = np.asarray(dequantize(qt))
    return out if dtype is None else np.asarray(out, dtype)


def _raw_part(ct: _CTensor):
    """Storage-form view for the jitted step: f32/bf16 entries are their
    payload array; int8 entries are the ``{"q", "scale"}`` dict that
    ``kernels.cached_step`` consumes (dequantised in VMEM, so both the
    host→device transfer and HBM reads stay at integer width)."""
    if ct.policy == "int8":
        return {"q": ct.data, "scale": ct.scale}
    return ct.data


def _part_nbytes(part) -> int:
    """Bytes of one storage-form part (an array or a q/scale dict)."""
    if isinstance(part, dict):
        return sum(a.nbytes for a in part.values())
    return part.nbytes


def _stack_parts(parts, axis: int):
    """Stack per-sequence storage-form parts (arrays or q/scale dicts)."""
    if isinstance(parts[0], dict):
        return {k: np.stack([p[k] for p in parts], axis=axis) for k in parts[0]}
    return np.stack(parts, axis=axis)


@jax.jit
def _join_on_device(seqs):
    """One batch from its sequences' device-resident parts: the device
    twin of :meth:`ActivationCache.get_batch`'s host stack — b0 and
    b_final on axis 0, taps (n_p, S, d) on axis 1, q/scale dicts leaf by
    leaf. A stack only copies, so the batch equals get_batch's bit for
    bit."""
    def stack(parts, axis):
        return jax.tree.map(lambda *xs: jnp.stack(xs, axis=axis), *parts)

    return tuple(stack(col, 1 if i == 1 else 0) for i, col in enumerate(zip(*seqs)))


@jax.jit
def _split_on_device(parts):
    """A batch's sequences, each part a device array of its own: the
    device twin of :func:`_ct_index` and the inverse of
    :func:`_join_on_device` — b0 and b_final split on axis 0, taps
    (n_p, B, S, d) on axis 1, q/scale dicts leaf by leaf. A slice only
    copies, so each piece equals the host slice bit for bit, and an entry
    made from it holds its own sequence's bytes and no more."""
    def take(i):
        return tuple(jax.tree.map(lambda x: x[:, i] if axis else x[i], part)
                     for axis, part in zip((0, 1, 0), parts))

    return [take(i) for i in range(jax.tree.leaves(parts[0])[0].shape[0])]


@dataclass
class _Pending:
    """The one batch whose host copy is in flight: its keys, each
    sequence's storage-form parts on the device (``_split_on_device``,
    every piece's device→host copy started) and each part's unpadded
    feature width."""

    keys: list
    seqs: list
    orig_lasts: tuple


@dataclass
class CacheEntry:
    """One sequence's cached activations: (b0, taps[, b_final])."""

    b0: _CTensor
    taps: _CTensor
    b_final: Optional[_CTensor] = None

    @property
    def nbytes(self) -> int:
        n = self.b0.nbytes + self.taps.nbytes
        return n + (0 if self.b_final is None else self.b_final.nbytes)

    def parts(self) -> Iterable[Tuple[str, _CTensor]]:
        yield "b0", self.b0
        yield "taps", self.taps
        if self.b_final is not None:
            yield "bf", self.b_final


def _entry_to_npz(entry: CacheEntry) -> Dict[str, np.ndarray]:
    meta = {}
    arrays: Dict[str, np.ndarray] = {}
    for name, ct in entry.parts():
        meta[name] = {"policy": ct.policy, "orig_last": ct.orig_last, "block": ct.block}
        arrays[name] = ct.data.view(np.uint16) if ct.policy == "bf16" else ct.data
        if ct.scale is not None:
            arrays[name + "_scale"] = ct.scale
    arrays["meta"] = np.array(json.dumps(meta))
    return arrays


def _entry_from_npz(z) -> CacheEntry:
    meta = json.loads(str(z["meta"]))

    def part(name: str) -> _CTensor:
        m = meta[name]
        data = z[name]
        if m["policy"] == "bf16":
            data = data.view(ml_dtypes.bfloat16)
        scale = z[name + "_scale"] if name + "_scale" in z.files else None
        return _CTensor(m["policy"], data, scale, m["orig_last"], m["block"])

    return CacheEntry(part("b0"), part("taps"), part("bf") if "bf" in meta else None)


# ---------------------------------------------------------------------------
# The cache manager
# ---------------------------------------------------------------------------


@dataclass
class ActivationCache:
    """Keyed store of backbone taps.

    Keys are sequence ids (ints). Values are (b0, taps[, b_final]) with
    shapes (S, d), (n_periods, S, d) and (S, d) — stored per-sequence so
    epochs can re-batch/shuffle freely, exactly like the paper's
    redistribution step. Entries are compressed per ``compress`` at put
    time; the byte budget covers compressed bytes. All mutating paths
    hold a lock so :class:`CachePrefetcher` can read from its own thread.

    At most one batch is in flight: :meth:`put_batch` with device arrays
    returns before they reach the host, and the next ``put_batch``
    completes them. Every read is a barrier that completes the batch in
    flight first (``get``/``get_batch`` of a key it may hold, ``covers``,
    ``in``, ``len``, ``keys``, ``nbytes``, ``flush``, ``save_manifest``,
    ``clear``, ``put``), so a put is visible to every read. Host span:
    ``pac.cache.drain`` (``nbytes``) around a barrier's completion.
    """

    budget_bytes: int = 2 << 30
    spill_dir: Optional[str] = None
    compress: str = "f32"
    _ram: Dict[int, CacheEntry] = field(default_factory=dict)
    _disk: Dict[int, str] = field(default_factory=dict)
    _final_absent: Set[int] = field(default_factory=set)
    _ram_bytes: int = 0
    hits: int = 0
    misses: int = 0
    _lock: threading.RLock = field(default_factory=threading.RLock, repr=False)
    _pending: Optional[_Pending] = field(default=None, repr=False)

    def __post_init__(self):
        if self.compress not in COMPRESS_POLICIES:
            raise ValueError(
                f"compress must be one of {COMPRESS_POLICIES}, got {self.compress!r}"
            )

    def __contains__(self, key: int) -> bool:
        with self._lock:
            self._drain()
            return key in self._ram or key in self._disk

    def __len__(self) -> int:
        # a promoted entry keeps its (clean) disk copy — count keys once
        return len(self.keys())

    @property
    def nbytes(self) -> int:
        with self._lock:
            self._drain()
            return self._ram_bytes

    def keys(self) -> Set[int]:
        with self._lock:
            self._drain()
            return self._ram.keys() | self._disk.keys()

    def covers(self, keys, with_final: bool = False) -> bool:
        """True when every key is resident (RAM or disk) — the gate for
        running an epoch through the prefetcher instead of the forward."""
        with self._lock:
            held = self.keys()
            return all(
                int(k) in held and not (with_final and int(k) in self._final_absent)
                for k in keys
            )

    # -- the batch in flight -------------------------------------------------

    def _complete(self) -> int:
        """Land the batch in flight, if any, and return its stored bytes:
        wait for each piece's host copy (``pac.cache.fetch``, ``nbytes``)
        and store every sequence's entry as fetched, with no host copy
        (``pac.cache.store``). The caller holds the lock."""
        pending, self._pending = self._pending, None
        if pending is None:
            return 0
        with TraceAnnotation("pac.cache.fetch") as fetch_span:
            entries = [
                CacheEntry(*(_host_ct(part, self.compress, last)
                             for part, last in zip(seq, pending.orig_lasts)))
                for seq in pending.seqs
            ]
            nbytes = sum(e.nbytes for e in entries)
            fetch_span.set_metadata(nbytes=nbytes)
        with TraceAnnotation("pac.cache.store"):
            for k, entry in zip(pending.keys, entries):
                self._put_entry(k, entry)
        return nbytes

    def _drain(self) -> None:
        """The read barrier: complete the batch in flight under
        ``pac.cache.drain`` (``nbytes``: its stored bytes)."""
        with self._lock:
            if self._pending is not None:
                with TraceAnnotation("pac.cache.drain") as drain_span:
                    drain_span.set_metadata(nbytes=self._complete())

    # -- writes ------------------------------------------------------------

    def put(self, key: int, b0, taps, b_final=None) -> None:
        entry = CacheEntry(
            _compress(b0, self.compress, own=True),
            _compress(taps, self.compress, own=True),
            None if b_final is None else _compress(b_final, self.compress, own=True),
        )
        with self._lock:
            self._drain()
            self._put_entry(key, entry)

    def _put_entry(self, key: int, entry: CacheEntry) -> None:
        size = entry.nbytes
        if entry.b_final is None:
            self._final_absent.add(key)
        else:
            self._final_absent.discard(key)
        # re-putting an existing key replaces it: retire the old entry's
        # bytes first, or the budget check double-counts and triggers
        # spurious evictions/spills
        if key in self._ram:
            old = self._ram.pop(key)
            self._ram_bytes -= old.nbytes
        if size > self.budget_bytes:
            # the entry alone exceeds the whole budget — don't flush the
            # hot working set making room that can't suffice: disk is its
            # home, or without a spill_dir it is dropped (one sequence
            # re-forwards later, instead of the whole RAM set)
            if self.spill_dir:
                self._spill(key, entry)
            return
        # LRU eviction: the *oldest* RAM entries move to disk, the new
        # entry stays RAM-resident — so under budget pressure the hot
        # (recently written/read) working set keeps serving from RAM
        # instead of freezing the earliest sequences there and routing
        # all later traffic through npz round-trips. Without a spill_dir
        # evicted entries are dropped (paper clears the cache
        # post-training; a mid-training drop means a re-forward later).
        self._evict_until(self.budget_bytes - size)
        if key in self._disk:  # new *data* for the key — the spill is stale
            path = self._disk.pop(key)
            try:
                os.remove(path)
            except OSError:
                pass
        self._ram[key] = entry
        self._ram_bytes += size

    def _evict_until(self, target_bytes: int) -> None:
        """Evict oldest RAM entries until ``_ram_bytes <= target_bytes``.
        A victim with a clean disk copy (promoted earlier) is dropped for
        free; otherwise it is spilled (or dropped without a spill_dir)."""
        while self._ram and self._ram_bytes > target_bytes:
            k, entry = next(iter(self._ram.items()))
            self._ram_bytes -= entry.nbytes
            del self._ram[k]
            if self.spill_dir and k not in self._disk:
                self._spill(k, entry)

    def _spill(self, key: int, entry: CacheEntry) -> None:
        os.makedirs(self.spill_dir, exist_ok=True)
        path = os.path.join(self.spill_dir, f"act_{key}.npz")
        np.savez(path, **_entry_to_npz(entry))
        self._disk[key] = path

    def flush(self) -> None:
        """Write every RAM entry without a clean disk copy to spill_dir —
        the persistence barrier before ``save_manifest``."""
        if not self.spill_dir:
            raise ValueError("flush() requires a spill_dir")
        with self._lock:
            self._drain()
            for k, entry in self._ram.items():
                if k not in self._disk:
                    self._spill(k, entry)

    # -- reads -------------------------------------------------------------

    def _get_entry(self, key: int, need_final: bool) -> Optional[CacheEntry]:
        with self._lock:
            # a key held nowhere, the batch in flight included, misses
            # before and after that batch lands: so a step's lookup of
            # its new keys does not wait for the previous step's fetch
            pending = self._pending
            if pending is not None and (
                    key in self._ram or key in self._disk
                    or key in pending.keys):
                self._drain()
            if need_final and key in self._final_absent:
                # present but incomplete for this request — the caller
                # re-forwards and re-puts with b_final (replacing the entry)
                self.misses += 1
                return None
            if key in self._ram:
                self.hits += 1
                # refresh recency so eviction order tracks access, not just
                # insertion (dicts iterate in insertion order)
                entry = self._ram.pop(key)
                self._ram[key] = entry
                return entry
            if key in self._disk:
                self.hits += 1
                # npz archives cannot be mmapped; close the zip handle rather
                # than leaking one file descriptor per disk hit
                with np.load(self._disk[key]) as z:
                    entry = _entry_from_npz(z)
                # promote the hit into RAM, *keeping* the npz as a clean copy:
                # evicting a promoted entry later is then free (no rewrite), so
                # the cyclic epoch sweep of a corpus larger than the budget
                # costs one read per miss — never a write per read
                size = entry.nbytes
                if size <= self.budget_bytes:
                    self._evict_until(self.budget_bytes - size)
                    self._ram[key] = entry
                    self._ram_bytes += size
                return entry
            self.misses += 1
            return None

    def get(self, key: int, with_final: bool = False, dtype=np.float32,
            compressed: bool = False):
        """Decompressed (b0, taps) — or (b0, taps, b_final) with
        ``with_final``; None on miss (including an entry stored without
        b_final when b_final is requested). ``dtype=None`` keeps bf16
        payloads compressed for the device transfer. ``compressed=True``
        skips host-side decompression entirely and returns each part in
        its storage form (int8 entries as ``{"q", "scale"}`` dicts) for
        a step that dequantizes on-device (``--kernels pallas``)."""
        entry = self._get_entry(int(key), need_final=with_final)
        if entry is None:
            return None
        parts = [entry.b0, entry.taps] + ([entry.b_final] if with_final else [])
        if compressed:
            return tuple(_raw_part(ct) for ct in parts)
        return tuple(_decompress(ct, dtype) for ct in parts)

    def put_batch(self, keys, b0, taps, b_final=None,
                  orig_last: Optional[int] = None) -> None:
        """b0: (B,S,d); taps: (n_p,B,S,d); b_final: (B,S,d). Each may
        arrive already in storage form — the int8 ``{"q", "scale"}`` dict
        a pallas OpSet emits at the tap site — and is adopted without
        recompression (``orig_last`` = the unpadded feature width, d).
        Compression runs once on the whole batch array: block-wise
        quantization along the last axis makes the payloads bit-identical
        to per-sequence compression at 1/B the dispatch overhead.

        Device arrays (epoch 1's outputs) are split into their sequences
        on the device (``_split_on_device``), each piece's device→host
        copy is started, and the batch is left in flight: this returns
        without waiting, so the copy runs behind the next step. The next
        ``put_batch`` completes it — the entries land as fetched, with no
        host copy — and any read completes it first (the class's
        barrier), so at most one batch is in flight and a put is visible
        to every read. Host arrays have no copy to hide: their entries
        are sliced (with copies) out of the batch and stored here.

        Host spans: ``pac.cache.put_batch`` holds ``pac.cache.fetch``
        (waiting for the host copy of the batch it completes, or for host
        arrays, compression) and ``pac.cache.store`` (storing the entries,
        eviction and spill included); the fetch carries ``nbytes``, the
        stored batch's bytes."""
        parts = (b0, taps) if b_final is None else (b0, taps, b_final)
        with TraceAnnotation("pac.cache.put_batch"):
            if all(isinstance(x, jax.Array) for x in jax.tree.leaves(parts)):
                forms = [_storage_form(p, self.compress, orig_last) for p in parts]
                seqs = _split_on_device(tuple(part for part, _ in forms))
                for piece in jax.tree.leaves(seqs):
                    piece.copy_to_host_async()
                with self._lock:
                    self._complete()
                    self._pending = _Pending(
                        [int(k) for k in keys], seqs, tuple(last for _, last in forms))
                return
            with self._lock:
                self._complete()
            with TraceAnnotation("pac.cache.fetch") as fetch_span:
                cts = [_compress(p, self.compress, orig_last=orig_last) for p in parts]
                fetch_span.set_metadata(nbytes=sum(ct.nbytes for ct in cts))
            with TraceAnnotation("pac.cache.store"):
                for i, k in enumerate(keys):
                    entry = CacheEntry(*(_ct_index(ct, (slice(None), i) if j == 1 else i)
                                         for j, ct in enumerate(cts)))
                    with self._lock:
                        self._put_entry(int(k), entry)

    def get_batch(self, keys, with_final: bool = False, dtype=np.float32,
                  compressed: bool = False):
        """Reassemble a training batch from cached sequences.

        ``compressed=True`` hands back storage-form parts (see
        :meth:`get`): the int8 policy yields ``{"q": (B,S,·) int8,
        "scale": (B,S,·) f32}`` dicts instead of dequantized arrays —
        the payload ``repro.kernels.cached_step`` dequantizes in VMEM."""
        items = [
            self.get(int(k), with_final=with_final, dtype=dtype,
                     compressed=compressed)
            for k in keys
        ]
        if any(it is None for it in items):
            return None
        b0 = _stack_parts([it[0] for it in items], axis=0)  # (B,S,d)
        taps = _stack_parts([it[1] for it in items], axis=1)  # (n_p,B,S,d)
        if not with_final:
            return b0, taps
        bf = _stack_parts([it[2] for it in items], axis=0)  # (B,S,d)
        return b0, taps, bf

    def clear(self) -> None:
        """Drop every entry and spill file; the batch in flight is
        fetched and stored first, so every put batch reaches the host."""
        with self._lock:
            self._drain()
            for path in self._disk.values():
                try:
                    os.remove(path)
                except OSError:
                    pass
            self._ram.clear()
            self._disk.clear()
            self._final_absent.clear()
            self._ram_bytes = 0

    # -- cross-run persistence ---------------------------------------------

    def save_manifest(self, meta: dict) -> str:
        """Flush all entries to spill_dir and write the manifest that lets
        a later run resume warm (``open_persistent``). ``meta`` is the
        caller's identity record — corpus/backbone fingerprints,
        compression policy knobs — compared verbatim on reopen."""
        self.flush()
        with self._lock:
            entries = {
                str(k): {
                    "file": os.path.basename(self._disk[k]),
                    "has_final": k not in self._final_absent,
                }
                for k in sorted(self.keys())
            }
            manifest = {
                "version": MANIFEST_VERSION,
                "compress": self.compress,
                "meta": meta,
                "entries": entries,
            }
            path = os.path.join(self.spill_dir, MANIFEST_NAME)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(manifest, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
            return path


def manifest_for(cfg, *, reduced, seq_len, quant_bits, backbone,
                 corpus_tokens) -> dict:
    """The cache-manifest identity dict, shared by every persistent-cache
    consumer (the trainer/session and the persistent-cache docs demo).

    Any change to the backbone weights (seed, quantization), the corpus
    contents, or the shapes changes a fingerprint here and invalidates
    the cache on reopen — ``open_persistent`` compares this dict
    verbatim against the stored manifest's ``meta``."""
    from repro.checkpoint import tree_fingerprint

    return {
        "arch": cfg.name,
        "reduced": bool(reduced),
        "seq": int(seq_len),
        "quant": int(quant_bits or 0),
        "backbone": tree_fingerprint(backbone),
        "corpus": tree_fingerprint(corpus_tokens),
    }


def _invalidate(cache_dir: str, reason: str) -> None:
    print(
        f"ACTIVATION CACHE INVALIDATED at {cache_dir}: {reason} — discarding "
        f"cached entries; epoch 1 will re-run the backbone forward",
        file=sys.stderr,
    )
    for name in os.listdir(cache_dir):
        if name == MANIFEST_NAME or (name.startswith("act_") and name.endswith(".npz")):
            try:
                os.remove(os.path.join(cache_dir, name))
            except OSError:
                pass


def open_persistent(
    cache_dir: str,
    meta: dict,
    *,
    budget_bytes: int = 2 << 30,
    compress: str = "f32",
) -> Tuple[ActivationCache, bool]:
    """Open (or create) a persistent cache at ``cache_dir``.

    Returns ``(cache, warm)``. ``warm`` is True iff a manifest exists and
    validates against ``meta`` + ``compress`` with every entry file
    present — the cache's disk index is then pre-populated and an epoch
    over the manifest's keys performs zero backbone forwards. Any
    mismatch invalidates loudly (stderr) and removes the stale entries.
    """
    cache = ActivationCache(
        budget_bytes=budget_bytes, spill_dir=cache_dir, compress=compress
    )
    path = os.path.join(cache_dir, MANIFEST_NAME)
    if not os.path.exists(path):
        return cache, False
    try:
        with open(path) as f:
            m = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        _invalidate(cache_dir, f"unreadable manifest ({e})")
        return cache, False
    if m.get("version") != MANIFEST_VERSION:
        _invalidate(cache_dir, f"manifest version {m.get('version')} != {MANIFEST_VERSION}")
        return cache, False
    if m.get("compress") != compress:
        _invalidate(
            cache_dir, f"compression policy changed ({m.get('compress')} -> {compress})"
        )
        return cache, False
    if m.get("meta") != meta:
        changed = sorted(
            k
            for k in set(m.get("meta", {})) | set(meta)
            if m.get("meta", {}).get(k) != meta.get(k)
        )
        _invalidate(cache_dir, f"meta mismatch on {changed}")
        return cache, False
    entries = m.get("entries", {})
    files = {k: os.path.join(cache_dir, v["file"]) for k, v in entries.items()}
    missing = [k for k, p in files.items() if not os.path.exists(p)]
    if missing:
        _invalidate(cache_dir, f"{len(missing)} entry file(s) missing")
        return cache, False
    for k, v in entries.items():
        cache._disk[int(k)] = files[k]
        if not v.get("has_final", False):
            cache._final_absent.add(int(k))
    return cache, True


# ---------------------------------------------------------------------------
# Async prefetch
# ---------------------------------------------------------------------------


class CachePrefetcher:
    """Background loader for cached epochs (paper Fig. 11's pure-DP phase).

    Iterates the epoch's known batch order (``DataPipeline.epoch_order``)
    on a daemon thread, so npz reads and dequantisation of batch *k+1*
    overlap train step *k*. The bounded queue (``depth``, default 2)
    double-buffers: one batch in flight while one is being consumed, and
    the thread blocks rather than loading the whole epoch ahead.

    Where the batch is joined depends on ``to_device``. With
    ``to_device=True`` it is joined on the device: the worker reads each
    sequence's parts as :meth:`ActivationCache.get` returns them (in
    storage form, the entry's own arrays: no host copy) and enqueues
    their host→device copy with one ``jax.device_put``; the consumer
    stacks the pieces with one jitted join right after the queue get, on
    its own thread, so the join queues behind the step before it. With
    ``to_device=False`` it is joined on the host by
    :meth:`ActivationCache.get_batch`, and the batch stays in host
    memory.

    Yields one ``(b0, taps[, b_final])`` tuple per key-batch, in order —
    or ``None`` for a batch with a missing key (the consumer falls back
    to the forward path). With ``compressed=True`` each part is yielded
    in its *storage* form (int8 entries as ``{"q", "scale"}`` dicts) so
    the device transfer stays at integer width and the Pallas cached
    step dequantizes in VMEM. While a prefetcher is draining, the owning
    thread must not mutate the cache except via ``put`` (both sides take
    the cache lock).

    A prefetcher is a context manager: ``with CachePrefetcher(...) as
    pf:`` guarantees deterministic shutdown on exit — including an
    exception mid-epoch — via :meth:`close` (signal the worker to stop,
    drain the queue so a blocked ``put`` unblocks, join the thread). A
    leaked worker would otherwise keep device buffers alive through its
    queued ``device_put`` results until process exit.

    Host spans: ``pac.prefetch.wait`` on the consumer's thread around the
    blocking queue get (``n``: the batch's ordinal, 0 for the first); the
    device join's dispatch follows it, under the caller's span
    (``pac.step.lookup`` in ``EdgeSession.step``). ``pac.prefetch.load``
    (the cache reads, and the host join for ``to_device=False``) and
    ``pac.prefetch.device_put`` on the worker (the latter with
    ``nbytes``, the batch's storage-form bytes).
    """

    _DONE = object()

    def __init__(
        self,
        cache: ActivationCache,
        key_batches: Sequence[np.ndarray],
        *,
        with_final: bool = True,
        depth: int = 2,
        to_device: bool = True,
        dtype=np.float32,
        compressed: bool = False,
    ):
        self._cache = cache
        self._key_batches = list(key_batches)
        self._with_final = with_final
        self._to_device = to_device
        self._dtype = dtype
        self._compressed = compressed
        self._q: queue.Queue = queue.Queue(maxsize=max(1, depth))
        self._err: Optional[BaseException] = None
        self._stop = threading.Event()
        self._done = False    # consumer saw the _DONE sentinel
        self._n_taken = 0     # items the consumer has taken
        self._closed = False  # close() ran — iteration must fail fast
        self._thread = threading.Thread(
            target=self._worker, name="activation-cache-prefetch", daemon=True
        )
        self._thread.start()

    def _worker(self) -> None:
        try:
            for keys in self._key_batches:
                if self._stop.is_set():
                    break
                with TraceAnnotation("pac.prefetch.load"):
                    got = self._load(keys)
                if got is not None and self._to_device:
                    # device_put handles the storage-form pytrees too
                    # ({"q","scale"} dicts ship at integer width); the
                    # span covers the enqueue, not the copy's end
                    nbytes = sum(_part_nbytes(p) for seq in got for p in seq)
                    with TraceAnnotation("pac.prefetch.device_put", nbytes=nbytes):
                        got = jax.device_put(got)
                self._q.put(got)
        except BaseException as e:  # surfaced on the consumer side
            self._err = e
        finally:
            self._q.put(self._DONE)

    def _load(self, keys):
        """The batch for ``keys``, or None if a key misses. For the host,
        the batch ``get_batch`` joins; for the device, each sequence's
        parts as ``get`` returns them, which ``__next__`` joins there."""
        kw = dict(with_final=self._with_final, dtype=self._dtype,
                  compressed=self._compressed)
        if not self._to_device:
            return self._cache.get_batch(keys, **kw)
        seqs = [self._cache.get(int(k), **kw) for k in keys]
        return None if any(seq is None for seq in seqs) else seqs

    def __iter__(self):
        return self

    def __next__(self):
        if self._closed:
            # after close() the queue is drained and the worker is gone —
            # a blocking get() here would hang forever. Elastic resharding
            # (repro.fleet) closes mid-epoch and re-opens over the
            # remaining order; a stale iterator must fail loudly instead.
            raise RuntimeError(
                "CachePrefetcher iterated after close(); open a new "
                "prefetcher over the remaining key batches")
        # n: this batch's ordinal in the prefetcher (0: an epoch's first)
        with TraceAnnotation("pac.prefetch.wait", n=self._n_taken):
            item = self._q.get()
        self._n_taken += 1
        if item is self._DONE:
            self._done = True
            if self._err is not None:
                raise self._err
            raise StopIteration
        if item is not None and self._to_device:
            item = _join_on_device(item)
        return item

    def __enter__(self) -> "CachePrefetcher":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    def close(self) -> None:
        """Deterministic shutdown: signal the worker to stop, drain the
        queue until its ``_DONE`` sentinel (unblocking a worker stuck on
        a full queue), and join the thread. Idempotent; safe mid-epoch
        (early exit / exception) and after normal exhaustion. Unlike
        iteration, a worker error is swallowed here — close() is for
        unwinding, not for results."""
        self._closed = True
        self._stop.set()
        while not self._done:
            try:
                item = self._q.get(timeout=60)
            except queue.Empty:  # worker wedged — join below, best effort
                break
            if item is self._DONE:
                self._done = True
        self._thread.join(timeout=30)
