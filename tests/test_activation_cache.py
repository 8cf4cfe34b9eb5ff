"""Regression tests for ActivationCache byte accounting and fd hygiene,
plus the v2 surface: compressed entries, folded b_final, async prefetch,
and cross-run persistence."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.activation_cache import (
    ActivationCache,
    CachePrefetcher,
    MANIFEST_NAME,
    cache_bytes_per_sequence,
    manifest_for,
    open_persistent,
    policy_bytes_per_value,
)


def _entry(seed, S=8, d=4, n_p=2):
    b0 = np.random.RandomState(seed).randn(S, d).astype(np.float32)
    taps = np.random.RandomState(100 + seed).randn(n_p, S, d).astype(np.float32)
    return b0, taps


def _entry_bytes(S=8, d=4, n_p=2):
    return S * d * 4 + n_p * S * d * 4


def test_reput_same_key_does_not_inflate_ram_bytes():
    """Re-putting an existing key replaces it — bytes must not accumulate."""
    cache = ActivationCache(budget_bytes=1 << 20)
    b0, taps = _entry(0)
    for _ in range(5):
        cache.put(1, b0, taps)
    assert len(cache) == 1
    assert cache.nbytes == _entry_bytes()


def test_reput_does_not_trigger_spurious_eviction():
    """Epoch-style overwrite of every key must not evict anything: the
    replaced entry's bytes are retired before the budget check."""
    one = _entry_bytes()
    cache = ActivationCache(budget_bytes=3 * one)
    entries = {k: _entry(k) for k in range(3)}
    for rounds in range(3):  # 3 epochs of identical puts, exactly at budget
        for k, (b0, taps) in entries.items():
            cache.put(k, b0, taps)
        assert len(cache) == 3
        assert cache.nbytes == 3 * one
    for k, (b0, taps) in entries.items():
        got = cache.get(k)
        np.testing.assert_array_equal(got[0], b0)
        np.testing.assert_array_equal(got[1], taps)


def test_reput_updates_accounting_for_new_size():
    cache = ActivationCache(budget_bytes=1 << 20)
    cache.put(7, *_entry(0, S=8))
    cache.put(7, *_entry(1, S=16))  # replace with a bigger entry
    assert cache.nbytes == _entry_bytes(S=16)
    cache.put(7, *_entry(2, S=4))  # and a smaller one
    assert cache.nbytes == _entry_bytes(S=4)


def test_reput_of_spilled_key_drops_stale_disk_entry(tmp_path):
    """A key that spilled to disk and is later re-put into RAM must not be
    double-counted by len() nor leave an orphan spill file."""
    one = _entry_bytes()
    cache = ActivationCache(budget_bytes=one + 1, spill_dir=str(tmp_path))
    cache.put(0, *_entry(0))
    cache.put(1, *_entry(1))  # over budget -> spills to disk
    assert len(cache) == 2
    assert len(list(tmp_path.iterdir())) == 1
    # shrink both entries so they fit in RAM: the spilled key must move
    # back, deleting its stale spill file
    cache.put(0, *_entry(2, S=1))
    cache.put(1, *_entry(3, S=1))
    assert len(cache) == 2
    assert cache.nbytes == 2 * _entry_bytes(S=1)
    assert list(tmp_path.iterdir()) == []


def test_disk_get_closes_npz_handle(tmp_path, monkeypatch):
    """The disk path of get() must close the npz archive it opens.

    Tracked per-instance via a wrapped np.load (patching NpzFile.close
    on the class segfaults numpy's __del__ during monkeypatch undo).
    """
    closed = []
    opened = []
    real_load = np.load

    def tracking_load(*args, **kwargs):
        z = real_load(*args, **kwargs)
        real_close = z.close
        def close_once():
            if z not in closed:
                closed.append(z)
            real_close()
        z.close = close_once  # instance attr shadows the method
        opened.append(z)
        return z

    monkeypatch.setattr(np, "load", tracking_load)
    cache = ActivationCache(budget_bytes=1, spill_dir=str(tmp_path))
    b0, taps = _entry(3)
    cache.put(5, b0, taps)  # budget 1 byte -> straight to disk
    got_b0, got_taps = cache.get(5)
    np.testing.assert_array_equal(got_b0, b0)
    np.testing.assert_array_equal(got_taps, taps)
    assert opened, "disk get should have gone through np.load"
    assert closed == opened, "get() must close the npz archive it opened"
    for z in opened:  # break the z -> close_once -> z ref cycle
        del z.close


def test_eviction_spills_oldest_keeps_recent_in_ram(tmp_path):
    """Under budget pressure the *oldest* RAM entries move to disk and the
    new entry stays RAM-resident — later traffic must not be frozen out
    of RAM by the earliest sequences (the pre-fix policy spilled every
    new entry once RAM filled)."""
    one = _entry_bytes()
    cache = ActivationCache(budget_bytes=2 * one, spill_dir=str(tmp_path))
    for k in range(6):
        cache.put(k, *_entry(k))
    # the two most recent keys are in RAM, the four oldest on disk
    assert set(cache._ram) == {4, 5}
    assert set(cache._disk) == {0, 1, 2, 3}
    assert cache.nbytes <= 2 * one
    for k in range(6):  # nothing was dropped
        got = cache.get(k)
        ref = _entry(k)
        np.testing.assert_array_equal(got[0], ref[0])
        np.testing.assert_array_equal(got[1], ref[1])


def test_disk_hit_promoted_back_to_ram(tmp_path, monkeypatch):
    """A disk hit is promoted into RAM so a re-read serves from memory; the
    npz stays behind as a *clean* copy, so evicting the promoted entry
    later is free and a cyclic sweep of an over-budget corpus never pays
    a write per read."""
    one = _entry_bytes()
    cache = ActivationCache(budget_bytes=2 * one, spill_dir=str(tmp_path))
    for k in range(4):
        cache.put(k, *_entry(k))
    assert 0 in cache._disk
    got = cache.get(0)
    np.testing.assert_array_equal(got[0], _entry(0)[0])
    assert 0 in cache._ram  # promoted (clean disk copy kept)
    assert cache.nbytes <= 2 * one
    assert len(cache) == 4  # overlap not double-counted
    # second read must not touch disk
    loads = []
    real_load = np.load
    monkeypatch.setattr(np, "load", lambda *a, **k: loads.append(a) or real_load(*a, **k))
    got2 = cache.get(0)
    np.testing.assert_array_equal(got2[0], _entry(0)[0])
    assert loads == []
    # epoch sweeps over the over-budget corpus: the first sweep may spill
    # still-dirty entries once; after that every entry has a clean disk
    # copy, so promotions/evictions never write again (mtimes stay fixed)
    for k in range(4):
        cache.get(k)  # warm-up sweep
    mtimes = {p: os.path.getmtime(p) for p in map(str, tmp_path.iterdir())}
    for _ in range(2):
        for k in range(4):
            got = cache.get(k)
            np.testing.assert_array_equal(got[0], _entry(k)[0])
    after = {p: os.path.getmtime(p) for p in map(str, tmp_path.iterdir())}
    assert after == mtimes, "promotion must not rewrite clean spill files"


def test_oversized_entry_spills_without_flushing_ram(tmp_path):
    """An entry larger than the whole budget goes straight to disk — it
    must not evict the (hot) RAM working set to make room that can never
    suffice."""
    one = _entry_bytes()
    cache = ActivationCache(budget_bytes=2 * one, spill_dir=str(tmp_path))
    cache.put(0, *_entry(0))
    cache.put(1, *_entry(1))
    cache.put(99, *_entry(9, S=64))  # 8x the budget
    assert set(cache._ram) == {0, 1}, "hot set must survive an oversized put"
    assert 99 in cache._disk
    got = cache.get(99)
    np.testing.assert_array_equal(got[0], _entry(9, S=64)[0])


def test_ram_hit_refreshes_recency(tmp_path):
    """Reading a RAM entry protects it from the next eviction round."""
    one = _entry_bytes()
    cache = ActivationCache(budget_bytes=2 * one, spill_dir=str(tmp_path))
    cache.put(0, *_entry(0))
    cache.put(1, *_entry(1))
    cache.get(0)  # 0 is now more recent than 1
    cache.put(2, *_entry(2))  # evicts 1, not 0
    assert set(cache._ram) == {0, 2}
    assert set(cache._disk) == {1}


def test_eviction_without_spill_dir_still_drops_oldest():
    one = _entry_bytes()
    cache = ActivationCache(budget_bytes=2 * one)
    for k in range(3):
        cache.put(k, *_entry(k))
    assert set(cache._ram) == {1, 2}
    assert cache.get(0) is None  # dropped, not spilled: re-forward later


def test_oversized_entry_without_spill_dir_keeps_hot_set():
    """No spill_dir: an over-budget entry is dropped (one re-forward),
    not inserted by flushing every hot entry (N re-forwards)."""
    one = _entry_bytes()
    cache = ActivationCache(budget_bytes=2 * one)
    cache.put(0, *_entry(0))
    cache.put(1, *_entry(1))
    cache.put(99, *_entry(9, S=64))  # 8x the budget
    assert set(cache._ram) == {0, 1}
    assert cache.get(99) is None
    assert cache.nbytes == 2 * one


def test_disk_hit_survives_spill_file_rewrite(tmp_path):
    """Repeated spills of the same key overwrite in place (no dup files)."""
    cache = ActivationCache(budget_bytes=1, spill_dir=str(tmp_path))
    cache.put(9, *_entry(0))
    b0, taps = _entry(4)
    cache.put(9, b0, taps)
    assert len(list(tmp_path.iterdir())) == 1
    got = cache.get(9)
    np.testing.assert_array_equal(got[0], b0)
    np.testing.assert_array_equal(got[1], taps)


# ---------------------------------------------------------------------------
# v2: compressed entries + folded b_final
# ---------------------------------------------------------------------------


def _entry_f(seed, S=8, d=256, n_p=2):
    rng = np.random.RandomState(seed)
    return (
        rng.randn(S, d).astype(np.float32),
        rng.randn(n_p, S, d).astype(np.float32),
        rng.randn(S, d).astype(np.float32),
    )


@pytest.mark.parametrize("policy", ["f32", "bf16", "int8"])
def test_policy_roundtrip_tolerance(policy):
    """f32 exact; bf16 within 2^-8 relative; int8 within the blockwise
    absmax/127 half-step bound (same scheme as the weight quantizer)."""
    cache = ActivationCache(budget_bytes=1 << 24, compress=policy)
    b0, taps, bf = _entry_f(0)
    cache.put(1, b0, taps, bf)
    got = cache.get(1, with_final=True)
    for ref, out in zip((b0, taps, bf), got):
        assert out.shape == ref.shape and out.dtype == np.float32
        if policy == "f32":
            np.testing.assert_array_equal(out, ref)
        elif policy == "bf16":
            assert np.max(np.abs(out - ref)) <= 2.0**-8 * np.max(np.abs(ref)) + 1e-6
        else:
            bound = np.max(np.abs(ref)) / 127 * 0.51 + 1e-6
            assert np.max(np.abs(out - ref)) <= bound


def test_invalid_policy_rejected():
    with pytest.raises(ValueError):
        ActivationCache(compress="fp8")


def test_compressed_nbytes_budget_accounting():
    """The budget covers *compressed* bytes: int8 ≥3x smaller than f32,
    bf16 exactly half (scale overhead included for int8)."""
    sizes = {}
    for policy in ("f32", "bf16", "int8"):
        cache = ActivationCache(budget_bytes=1 << 24, compress=policy)
        cache.put(1, *_entry_f(0))
        sizes[policy] = cache.nbytes
    assert sizes["bf16"] * 2 == sizes["f32"]
    assert sizes["int8"] * 3 < sizes["f32"]
    # and the analytic per-value model matches the measured bytes
    n_values = sum(a.size for a in _entry_f(0))
    for policy, nb in sizes.items():
        assert nb == pytest.approx(n_values * policy_bytes_per_value(policy), rel=0.01)


def test_b_final_folded_into_entry_accounting():
    """b_final rides in the same budgeted entry as b0/taps (ISSUE 3: the
    trainer's former side dict was unbudgeted and never spilled)."""
    cache = ActivationCache(budget_bytes=1 << 24)
    b0, taps, bf = _entry_f(0)
    cache.put(1, b0, taps)
    without = cache.nbytes
    cache.put(1, b0, taps, bf)
    assert cache.nbytes == without + bf.nbytes


def test_with_final_miss_when_entry_lacks_it():
    cache = ActivationCache(budget_bytes=1 << 24)
    b0, taps, bf = _entry_f(0)
    cache.put(1, b0, taps)  # legacy two-part entry
    assert cache.get(1) is not None
    assert cache.get(1, with_final=True) is None  # incomplete -> miss
    assert cache.misses == 1
    cache.put(1, b0, taps, bf)  # re-put replaces with the full entry
    got = cache.get(1, with_final=True)
    np.testing.assert_array_equal(got[2], bf)


@pytest.mark.parametrize("policy", ["f32", "bf16", "int8"])
def test_policy_spill_roundtrip_bit_exact(policy, tmp_path):
    """Disk round-trip preserves the *compressed* payload bit-exactly:
    RAM-served and npz-served reads decompress identically."""
    cache = ActivationCache(budget_bytes=1 << 24, compress=policy,
                            spill_dir=str(tmp_path))
    b0, taps, bf = _entry_f(3)
    cache.put(7, b0, taps, bf)
    from_ram = cache.get(7, with_final=True)
    cache.flush()
    cache._ram.clear()
    cache._ram_bytes = 0
    from_disk = cache.get(7, with_final=True)
    for a, b in zip(from_ram, from_disk):
        np.testing.assert_array_equal(a, b)


def test_get_batch_with_final_and_raw_dtype():
    cache = ActivationCache(budget_bytes=1 << 24, compress="bf16")
    b0 = np.random.RandomState(0).randn(4, 8, 32).astype(np.float32)
    taps = np.random.RandomState(1).randn(2, 4, 8, 32).astype(np.float32)
    bf = np.random.RandomState(2).randn(4, 8, 32).astype(np.float32)
    cache.put_batch([0, 1, 2, 3], b0, taps, bf)
    got = cache.get_batch([2, 0], with_final=True)
    assert got[0].shape == (2, 8, 32) and got[1].shape == (2, 2, 8, 32)
    assert all(g.dtype == np.float32 for g in got)
    # dtype=None ships bf16 payloads raw (half the host->device bytes);
    # the cached train step upcasts on device
    raw = cache.get_batch([2, 0], with_final=True, dtype=None)
    import ml_dtypes

    assert all(g.dtype == ml_dtypes.bfloat16 for g in raw)
    np.testing.assert_array_equal(
        np.asarray(raw[0], np.float32), got[0]
    )


# ---------------------------------------------------------------------------
# v2: async prefetch
# ---------------------------------------------------------------------------


def _filled_cache(n=8, spill_dir=None, budget=1 << 24, compress="f32"):
    cache = ActivationCache(budget_bytes=budget, spill_dir=spill_dir, compress=compress)
    for k in range(n):
        cache.put(k, *_entry_f(k, d=32))
    return cache


@pytest.mark.parametrize("to_device", [False, True], ids=["host", "device"])
@pytest.mark.parametrize("policy,dtype,compressed", [
    ("f32", np.float32, False),
    ("bf16", None, False),
    ("int8", np.float32, False),
    ("int8", None, True),
], ids=["f32", "bf16-raw", "int8", "int8-storage"])
def test_prefetcher_matches_sync_reads(tmp_path, to_device, policy, dtype, compressed):
    """The prefetcher yields exactly what synchronous get_batch returns,
    in batch order: the same tree, shapes, dtypes and bits, whether the
    batch is joined on the host or on the device — including entries
    that must come off disk and a short last batch."""
    sizer = _filled_cache(1, compress=policy)
    cache = _filled_cache(11, spill_dir=str(tmp_path), budget=3 * sizer.nbytes,
                          compress=policy)
    order = [np.array([0, 5, 9, 2]), np.array([7, 4, 1, 10]), np.array([6, 3, 8])]
    kw = dict(with_final=True, dtype=dtype, compressed=compressed)
    want = [cache.get_batch(keys, **kw) for keys in order]
    got = list(CachePrefetcher(cache, order, to_device=to_device, **kw))
    assert len(got) == len(want)
    for w, g in zip(want, got):
        assert jax.tree.structure(g) == jax.tree.structure(w)
        for a, b in zip(jax.tree.leaves(w), jax.tree.leaves(g)):
            assert isinstance(b, jax.Array) == to_device
            b = np.asarray(b)
            assert (b.shape, b.dtype) == (a.shape, a.dtype)
            assert b.tobytes() == a.tobytes()
    assert [jax.tree.leaves(g[0])[0].shape[0] for g in got] == [4, 4, 3]


class _HostStack(Exception):
    pass


def test_prefetcher_joins_on_the_device_without_a_host_stack(monkeypatch):
    """The device-bound prefetcher never stacks a batch on the host; the
    host-bound one does, through get_batch."""
    from repro.core import activation_cache

    def refuse(parts, axis):
        raise _HostStack

    monkeypatch.setattr(activation_cache, "_stack_parts", refuse)
    cache = _filled_cache(6, compress="int8")
    order = [np.array([0, 1, 2, 3]), np.array([4, 5])]
    got = list(CachePrefetcher(cache, order, to_device=True, compressed=True))
    assert [g[0]["q"].shape[0] for g in got] == [4, 2]
    with pytest.raises(_HostStack):
        list(CachePrefetcher(cache, order, to_device=False, compressed=True))


def test_prefetcher_device_put_yields_jax_arrays():
    import jax

    cache = _filled_cache(4)
    order = [np.array([0, 1]), np.array([2, 3])]
    got = list(CachePrefetcher(cache, order, to_device=True))
    assert all(isinstance(part, jax.Array) for batch in got for part in batch)


def test_prefetcher_bounded_queue_blocks_ahead():
    """depth=1 must not race through the whole epoch before consumption —
    the worker blocks on the bounded queue (double-buffering, not
    load-everything)."""
    cache = _filled_cache(8)
    order = [np.array([k]) for k in range(8)]
    pf = CachePrefetcher(cache, order, to_device=False, depth=1)
    deadline = time.time() + 5
    while pf._q.qsize() < 1 and time.time() < deadline:
        time.sleep(0.01)
    time.sleep(0.05)  # give the worker a chance to (wrongly) run ahead
    # at most depth items buffered + one blocked in-flight inside put()
    assert pf._q.qsize() <= 2
    assert len(list(pf)) == 8  # and draining still yields everything


def test_prefetcher_yields_none_on_missing_key():
    cache = _filled_cache(2)
    order = [np.array([0]), np.array([99]), np.array([1])]
    got = list(CachePrefetcher(cache, order, to_device=False))
    assert got[1] is None
    assert got[0] is not None and got[2] is not None


def test_prefetcher_context_manager_joins_worker_on_early_exit():
    """Abandoning an epoch mid-stream (exception, early break) must not
    leak the worker: `with` closes the prefetcher — stop flag, queue
    drain (so a blocked put() unblocks), thread join."""
    cache = _filled_cache(8)
    order = [np.array([k]) for k in range(8)]
    with pytest.raises(RuntimeError):
        with CachePrefetcher(cache, order, to_device=False, depth=1) as pf:
            assert next(pf) is not None  # consume one of eight
            raise RuntimeError("train step blew up")
    assert not pf._thread.is_alive()
    assert pf._q.qsize() == 0


def test_prefetcher_close_is_idempotent_and_safe_after_drain():
    cache = _filled_cache(4)
    order = [np.array([k]) for k in range(4)]
    with CachePrefetcher(cache, order, to_device=False) as pf:
        assert len(list(pf)) == 4  # fully drained: sentinel consumed
    assert not pf._thread.is_alive()
    pf.close()  # second close is a no-op
    # plain (non-`with`) use still works and can be closed manually
    pf2 = CachePrefetcher(cache, order, to_device=False)
    assert len(list(pf2)) == 4
    pf2.close()


# ---------------------------------------------------------------------------
# v2: the shared manifest identity
# ---------------------------------------------------------------------------


def test_manifest_for_fingerprints_backbone_and_corpus():
    """manifest_for is THE cache identity: same inputs → same dict;
    any backbone/corpus/shape change → different dict (invalidation)."""
    import types

    cfg = types.SimpleNamespace(name="demo-arch")
    backbone = {"w": np.arange(12, dtype=np.float32).reshape(3, 4)}
    corpus = np.arange(64, dtype=np.int32)
    m = manifest_for(cfg, reduced=True, seq_len=16, quant_bits=None,
                     backbone=backbone, corpus_tokens=corpus)
    assert m == manifest_for(cfg, reduced=True, seq_len=16, quant_bits=None,
                             backbone=backbone, corpus_tokens=corpus)
    assert set(m) == {"arch", "reduced", "seq", "quant", "backbone", "corpus"}
    assert m["arch"] == "demo-arch" and m["quant"] == 0 and m["seq"] == 16
    m8 = manifest_for(cfg, reduced=True, seq_len=16, quant_bits=8,
                      backbone=backbone, corpus_tokens=corpus)
    assert m8["quant"] == 8
    other_bb = {"w": backbone["w"] + 1}
    assert manifest_for(cfg, reduced=True, seq_len=16, quant_bits=None,
                        backbone=other_bb, corpus_tokens=corpus) != m
    assert manifest_for(cfg, reduced=True, seq_len=16, quant_bits=None,
                        backbone=backbone, corpus_tokens=corpus + 1) != m


# ---------------------------------------------------------------------------
# v2: cross-run persistence
# ---------------------------------------------------------------------------


_META = {"backbone": "abc123", "corpus": "def456", "seq": 16}


def test_persistence_warm_reopen(tmp_path):
    cache, warm = open_persistent(str(tmp_path), _META, compress="int8")
    assert not warm
    b0, taps, bf = _entry_f(0)
    cache.put(3, b0, taps, bf)
    cache.put(5, b0, taps, bf)
    cache.save_manifest(_META)
    assert (tmp_path / MANIFEST_NAME).exists()

    cache2, warm2 = open_persistent(str(tmp_path), _META, compress="int8")
    assert warm2
    assert cache2.covers([3, 5], with_final=True)
    got = cache2.get(3, with_final=True)
    ref = cache.get(3, with_final=True)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(a, b)


def test_persistence_meta_mismatch_invalidates(tmp_path, capsys):
    cache, _ = open_persistent(str(tmp_path), _META)
    cache.put(1, *_entry_f(0))
    cache.save_manifest(_META)
    changed = dict(_META, backbone="zzz")
    cache2, warm = open_persistent(str(tmp_path), changed)
    assert not warm
    assert "INVALIDATED" in capsys.readouterr().err
    # stale entries and manifest are gone; a fresh save works
    assert not (tmp_path / MANIFEST_NAME).exists()
    assert not list(tmp_path.glob("act_*.npz"))


def test_persistence_policy_change_invalidates(tmp_path):
    cache, _ = open_persistent(str(tmp_path), _META, compress="f32")
    cache.put(1, *_entry_f(0))
    cache.save_manifest(_META)
    _, warm = open_persistent(str(tmp_path), _META, compress="bf16")
    assert not warm


def test_persistence_missing_entry_file_invalidates(tmp_path):
    cache, _ = open_persistent(str(tmp_path), _META)
    cache.put(1, *_entry_f(0))
    cache.put(2, *_entry_f(1))
    cache.save_manifest(_META)
    os.remove(str(tmp_path / "act_2.npz"))
    _, warm = open_persistent(str(tmp_path), _META)
    assert not warm


def test_persistence_records_final_absence(tmp_path):
    """Entries saved without b_final reopen as covers(with_final)=False,
    so a warm trainer knows it must re-forward them."""
    cache, _ = open_persistent(str(tmp_path), _META)
    b0, taps, bf = _entry_f(0)
    cache.put(1, b0, taps)  # no b_final
    cache.put(2, b0, taps, bf)
    cache.save_manifest(_META)
    cache2, warm = open_persistent(str(tmp_path), _META)
    assert warm
    assert cache2.covers([1, 2])
    assert cache2.covers([2], with_final=True)
    assert not cache2.covers([1, 2], with_final=True)


def test_entries_do_not_alias_the_batch_array():
    """A per-sequence entry must own its bytes: an f32 view of one row
    would pin the entire (n_p,B,S,d) batch array in RAM, making the byte
    budget meaningless (code-review regression)."""
    cache = ActivationCache(budget_bytes=1 << 24, compress="f32")
    B = 4
    b0 = np.random.RandomState(0).randn(B, 8, 32).astype(np.float32)
    taps = np.random.RandomState(1).randn(2, B, 8, 32).astype(np.float32)
    bf = np.random.RandomState(2).randn(B, 8, 32).astype(np.float32)
    cache.put_batch(list(range(B)), b0, taps, bf)
    for entry in cache._ram.values():
        for _, ct in entry.parts():
            assert ct.data.base is None, "entry payload is a view"
            assert not np.shares_memory(ct.data, taps)
            assert not np.shares_memory(ct.data, b0)
    # the single-sequence path owns its buffer too
    cache.put(99, b0[0], taps[:, 0], bf[0])
    for _, ct in cache._ram[99].parts():
        assert not np.shares_memory(ct.data, taps)
        assert not np.shares_memory(ct.data, b0)


@pytest.mark.parametrize("policy", ["f32", "bf16", "int8"])
def test_put_batch_matches_per_sequence_puts(policy):
    """Batch-level compression + slicing must be bit-identical to
    compressing each sequence separately (blocks run along the last
    axis, so they never straddle the sliced dims)."""
    B = 3
    b0 = np.random.RandomState(0).randn(B, 8, 200).astype(np.float32)
    taps = np.random.RandomState(1).randn(2, B, 8, 200).astype(np.float32)
    bf = np.random.RandomState(2).randn(B, 8, 200).astype(np.float32)
    batched = ActivationCache(budget_bytes=1 << 26, compress=policy)
    batched.put_batch(list(range(B)), b0, taps, bf)
    single = ActivationCache(budget_bytes=1 << 26, compress=policy)
    for i in range(B):
        single.put(i, b0[i], taps[:, i], bf[i])
    assert batched.nbytes == single.nbytes
    for i in range(B):
        for a, b in zip(
            batched.get(i, with_final=True), single.get(i, with_final=True)
        ):
            np.testing.assert_array_equal(a, b)


def test_cache_bytes_per_sequence_with_final():
    from repro.configs import get_arch

    cfg = get_arch("t5-base-pac")
    base = cache_bytes_per_sequence(cfg, 30)
    assert base == (cfg.n_periods + 1) * 30 * cfg.d_model * 4  # paper formula
    v2 = cache_bytes_per_sequence(
        cfg, 30, policy_bytes_per_value("int8"), with_final=True
    )
    assert v2 == int((cfg.n_periods + 2) * 30 * cfg.d_model * policy_bytes_per_value("int8"))


# ---------------------------------------------------------------------------
# prefetcher hardening for elastic resharding (repro.fleet)
# ---------------------------------------------------------------------------


def test_prefetcher_next_after_close_raises():
    """A stale iterator after close() must fail loudly — before the
    `_closed` flag a next() here blocked forever on the drained queue
    (the fleet reshard path closes mid-epoch)."""
    cache = _filled_cache(4)
    order = [np.array([k]) for k in range(4)]
    pf = CachePrefetcher(cache, order, to_device=False)
    assert next(pf) is not None
    pf.close()
    with pytest.raises(RuntimeError, match="after close"):
        next(pf)


def test_prefetcher_reshard_close_reopen_mid_epoch():
    """The elastic-reshard lifecycle: consume part of an epoch, close,
    re-open a fresh prefetcher over the remaining order. No deadlock, no
    leaked worker thread, and the stitched stream equals direct reads."""
    import threading

    def workers():
        return [t for t in threading.enumerate()
                if t.name == "activation-cache-prefetch" and t.is_alive()]

    cache = _filled_cache(8)
    order = [np.array([k, k + 1]) for k in range(0, 8, 2)]
    base = len(workers())

    pf = CachePrefetcher(cache, order, to_device=False, depth=1)
    got = [next(pf), next(pf)]
    pf.close()                                   # reshard point, mid-epoch
    assert len(workers()) == base                # worker joined, not leaked

    pf2 = CachePrefetcher(cache, order[2:], to_device=False, depth=1)
    got.extend(pf2)
    assert len(workers()) == base

    want = [cache.get_batch(keys, with_final=True) for keys in order]
    assert len(got) == len(want)
    for w, g in zip(want, got):
        for a, b in zip(w, g):
            np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# put_batch with device arrays: split on the device, fetched behind the
# next put, completed by every read
# ---------------------------------------------------------------------------


def _batches(policy, with_final, d=200):
    """Two host batches (4 sequences, then a short last one of 3) in the
    form put_batch takes for ``policy``: f32 arrays, or for
    "int8-storage" the {"q", "scale"} dicts a pallas OpSet emits (d is
    not a multiple of the block, so q is padded and orig_last matters)."""
    from repro.core.quantization import quantize

    out = []
    for seed, B in ((0, 4), (1, 3)):
        rng = np.random.RandomState(seed)
        parts = [rng.randn(B, 8, d), rng.randn(2, B, 8, d), rng.randn(B, 8, d)]
        parts = [p.astype(np.float32) for p in parts[:3 if with_final else 2]]
        if policy == "int8-storage":
            parts = [{"q": np.asarray(qt.q), "scale": np.asarray(qt.scale)}
                     for qt in (quantize(jnp.asarray(p), bits=8, block=128) for p in parts)]
        out.append(parts)
    return out


def _put_all(cache, batches, to_device, d=200):
    start = 0
    for parts in batches:
        B = jax.tree.leaves(parts[0])[0].shape[0]
        if to_device:
            parts = jax.device_put(parts)
        cache.put_batch(list(range(start, start + B)), *parts, orig_last=d)
        start += B
    return list(range(start))


def _same_entry(a, b):
    for (na, ca), (nb, cb) in zip(a.parts(), b.parts(), strict=True):
        assert na == nb
        assert (ca.policy, ca.orig_last, ca.block) == (cb.policy, cb.orig_last, cb.block)
        assert (ca.data.dtype, ca.data.shape) == (cb.data.dtype, cb.data.shape)
        assert ca.data.tobytes() == cb.data.tobytes()
        assert (ca.scale is None) == (cb.scale is None)
        if ca.scale is not None:
            assert ca.scale.tobytes() == cb.scale.tobytes()


@pytest.mark.parametrize("with_final", [False, True], ids=["no-final", "final"])
@pytest.mark.parametrize("policy", ["f32", "bf16", "int8", "int8-storage"])
def test_put_batch_from_device_matches_host(policy, with_final):
    """Device arrays are split on the device and left in flight; once
    landed, every entry equals the host path's bit for bit."""
    compress = "int8" if policy == "int8-storage" else policy
    batches = _batches(policy, with_final)
    host = ActivationCache(budget_bytes=1 << 26, compress=compress)
    keys = _put_all(host, batches, to_device=False)
    assert host._pending is None
    dev = ActivationCache(budget_bytes=1 << 26, compress=compress)
    _put_all(dev, batches, to_device=True)
    assert dev._pending is not None and dev._pending.keys == keys[4:]
    assert dev.nbytes == host.nbytes and dev._pending is None
    for k in keys:
        _same_entry(dev._ram[k], host._ram[k])


def _landed_like_host(cache, keys):
    want = ActivationCache(budget_bytes=1 << 26, compress=cache.compress)
    _put_all(want, _batches("int8", True), to_device=False)
    for k in keys:
        _same_entry(cache._ram[k], want._ram[k])


def _manifest_keys(cache, tmp_path):
    cache.flush()
    with open(cache.save_manifest({"run": 1})) as f:
        entries = json.load(f)["entries"]
    assert all(os.path.exists(tmp_path / e["file"]) for e in entries.values())
    return sorted(int(k) for k in entries)


_READS = {
    "get": lambda c, keys, _: c.get(keys[-1], with_final=True) is not None,
    "get_batch": lambda c, keys, _: c.get_batch(keys, with_final=True) is not None,
    "covers": lambda c, keys, _: c.covers(keys, with_final=True),
    "in": lambda c, keys, _: keys[-1] in c,
    "len": lambda c, keys, _: len(c) == len(keys),
    "keys": lambda c, keys, _: c.keys() == set(keys),
    "nbytes": lambda c, keys, _: c.nbytes == sum(e.nbytes for e in c._ram.values()) > 0,
    "flush+save_manifest": lambda c, keys, tmp: _manifest_keys(c, tmp) == keys,
    "prefetcher": lambda c, keys, _: all(
        b is not None for b in CachePrefetcher(c, [np.array(keys)], to_device=False)),
}


@pytest.mark.parametrize("read", sorted(_READS))
def test_every_read_sees_the_batch_in_flight(read, tmp_path):
    """Each read is a barrier: the batch put last, still in flight, is
    landed first and seen by the read — a prefetcher's worker reading
    right after the put included."""
    cache = ActivationCache(budget_bytes=1 << 26, compress="int8",
                            spill_dir=str(tmp_path))
    keys = _put_all(cache, _batches("int8", True), to_device=True)
    assert cache._pending is not None
    assert _READS[read](cache, keys, tmp_path)
    assert cache._pending is None
    _landed_like_host(cache, keys)


def test_lookup_of_new_keys_leaves_the_batch_in_flight():
    """A key held nowhere misses whether or not the batch in flight has
    landed, so its lookup does not wait for the fetch (a capture step
    looks up its own new keys before it runs); a key of the batch, or
    one already held, lands the batch first."""
    cache = ActivationCache(budget_bytes=1 << 26, compress="int8")
    first, second = _batches("int8", True)
    cache.put_batch([0, 1, 2, 3], *jax.device_put(first), orig_last=200)
    assert cache.get_batch([10, 11], with_final=True) is None
    assert cache.misses == 2 and cache._pending is not None
    assert cache.get(2, with_final=True) is not None and cache._pending is None
    cache.put_batch([4, 5, 6], *jax.device_put(second), orig_last=200)
    assert cache.get(0) is not None and cache._pending is None


def test_clear_lands_then_drops_the_batch_in_flight(tmp_path):
    """clear() after a device put leaves no entry, no byte and no spill
    file, though landing the batch spilled part of it."""
    cache = ActivationCache(budget_bytes=1 << 26, compress="int8",
                            spill_dir=str(tmp_path))
    sizer = ActivationCache(budget_bytes=1 << 26, compress="int8")
    _put_all(sizer, _batches("int8", True), to_device=False)
    cache.budget_bytes = 2 * sizer.nbytes // 7  # two of the seven entries
    _put_all(cache, _batches("int8", True), to_device=True)
    cache.clear()
    assert cache._pending is None
    assert len(cache) == 0 and cache.nbytes == 0 and not cache._final_absent
    assert os.listdir(tmp_path) == []


def _held_bytes(a):
    """Bytes of the buffer an array keeps alive through its base chain."""
    while isinstance(a.base, np.ndarray):
        a = a.base
    return a.nbytes if a.base is None else memoryview(a.base).nbytes


@pytest.mark.parametrize("policy", ["f32", "int8"])
def test_device_entries_hold_only_their_own_sequence(policy):
    """The device twin of test_entries_do_not_alias_the_batch_array: an
    entry landed from device arrays holds its own sequence's bytes and
    no more, with no host copy of the batch behind it."""
    cache = ActivationCache(budget_bytes=1 << 26, compress=policy)
    (parts, _) = _batches("f32", True)
    dev = jax.device_put(parts)
    cache.put_batch([0, 1, 2, 3], *dev)
    assert len(cache) == 4
    batch_views = [np.asarray(p) for p in dev]
    for entry in cache._ram.values():
        for _, ct in entry.parts():
            for a in (ct.data, ct.scale):
                if a is None:
                    continue
                assert _held_bytes(a) == a.nbytes
                assert not any(np.shares_memory(a, v) for v in batch_views)


@pytest.mark.parametrize("to_device", [False, True], ids=["host", "device"])
def test_storage_form_refused_before_anything_is_deferred(to_device):
    """A {"q", "scale"} dict under a non-int8 policy raises in put_batch
    itself, on both paths, and leaves nothing stored or in flight."""
    (parts, _) = _batches("int8-storage", True)
    cache = ActivationCache(budget_bytes=1 << 26, compress="f32")
    with pytest.raises(ValueError, match="int8 policy"):
        cache.put_batch([0, 1, 2, 3], *(jax.device_put(parts) if to_device else parts))
    assert cache._pending is None and len(cache) == 0
