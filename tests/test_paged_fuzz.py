"""Property-fuzz layer gating the fused paged-decode hot path.

Two fuzz surfaces, both seeded so every case is reproducible from its
pytest id:

* **Numerics**: the fused KV-write+attend launch
  (``kernels.paged_attention.fused_decode_write_attend``) must be
  bit-identical to the unfused ``write_token_page`` x2 ->
  ``paged_decode_attention`` composition *under the same impl*, on
  every active lane, across random geometries, formats
  (e4m3/e5m2/float), rounding modes (rne/rz/stochastic), write masks
  and impls (ref/batch/kernel) — including the updated cache arrays,
  not just the attention output.  (Cross-impl identity is pinned only
  at the canonical serving geometry, in tests/test_paged_serving.py:
  XLA CPU lowers score reductions shape-dependently, so batch and ref
  can differ by 1 ulp at arbitrary fuzz geometries — fused and unfused
  under one impl never do.)
* **Allocator**: randomized page-pool op sequences
  (alloc/grow/share/cow/free/spill/restore/seize) with
  ``PagePool.assert_invariants()`` after EVERY op, plus differential
  checks of the batched entry points (``ensure_capacity_batch``,
  ``writable_mask``) against their per-slot scalar forms.

Property tests proper use ``hypothesis``; the seeded sweeps run
alongside them.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.quant import encode
from repro.kernels.paged_attention import (
    fused_decode_write_attend,
    paged_decode_attention,
)
from repro.serving import PagePool, write_token_page


# --------------------------------------------------------------------------- #
# Fused == unfused, bit for bit, under random geometry/format/mode/mask
# --------------------------------------------------------------------------- #
def _random_case(seed, *, fmt):
    """Ownership-respecting random decode-step inputs.

    Every slot owns ``maxp`` distinct pages (the page-ownership contract:
    a slot's valid length must never exceed its owned capacity, or the
    in-flight insertion and the cache scatter legitimately disagree), and
    page contents are encoded from real floats — raw random uint8 codes
    would include NaN encodings.
    """
    rng = np.random.default_rng(seed)
    page = int(rng.choice([4, 8]))
    maxp = int(rng.integers(2, 5))
    B = int(rng.integers(1, 4))
    KV = int(rng.choice([1, 2]))
    G = int(rng.choice([1, 2]))
    H, hd = KV * G, int(rng.choice([4, 8]))
    P = B * maxp + 1
    bt = rng.permutation(np.arange(1, P)).reshape(B, maxp).astype(np.int32)
    # pre-write lengths: the written row must land inside owned capacity
    lengths = rng.integers(0, maxp * page, size=B).astype(np.int32)
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)).astype(np.float32))
    k_new = jnp.asarray(rng.standard_normal((B, KV, hd)).astype(np.float32))
    v_new = jnp.asarray(rng.standard_normal((B, KV, hd)).astype(np.float32))
    kf = rng.standard_normal((P, page, KV, hd)).astype(np.float32)
    vf = rng.standard_normal((P, page, KV, hd)).astype(np.float32)
    if fmt is None:
        kp, vp = jnp.asarray(kf), jnp.asarray(vf)
        ks = vs = jnp.ones((P,), jnp.float32)
    else:
        kp = encode(jnp.asarray(kf), fmt)
        vp = encode(jnp.asarray(vf), fmt)
        ks = jnp.asarray(2.0 ** rng.integers(-2, 3, size=P).astype(np.float32))
        vs = jnp.asarray(2.0 ** rng.integers(-2, 3, size=P).astype(np.float32))
    mask = rng.random(B) < 0.8
    if not mask.any():
        mask[0] = True
    window = int(rng.choice([0, 5]))
    cap = float(rng.choice([0.0, 25.0]))
    return dict(q=q, k_new=k_new, v_new=v_new, kp=kp, vp=vp, ks=ks, vs=vs,
                bt=jnp.asarray(bt), lengths=jnp.asarray(lengths),
                mask=mask, window=window, cap=cap, page=page, KV=KV)


def _unfused(case, *, fmt, mode, kv_mode, k_key, v_key, impl,
             interpret=None):
    """The write-then-attend oracle the fused launch must reproduce.

    The attend runs under the SAME impl as the fused launch being tested:
    the hot-path contract is fused == unfused per impl (what the engine's
    fused on/off toggle relies on).  Cross-impl identity (batch == ref ==
    kernel) is a separate property pinned at the canonical serving
    geometry by tests/test_paged_serving.py — XLA CPU lowers the score
    sums shape-dependently, so it does not hold for arbitrary fuzz
    geometries even in the unfused composition.
    """
    logical = case["lengths"] // case["page"]
    rows = case["lengths"] - logical * case["page"]
    page_ids = jnp.take_along_axis(
        case["bt"], logical[:, None], axis=1)[:, 0]
    wm = jnp.asarray(case["mask"])
    kp, ks = write_token_page(case["kp"], case["ks"], case["k_new"],
                              page_ids, rows, fmt=fmt, mode=kv_mode,
                              key=k_key, write_mask=wm)
    vp, vs = write_token_page(case["vp"], case["vs"], case["v_new"],
                              page_ids, rows, fmt=fmt, mode=kv_mode,
                              key=v_key, write_mask=wm)
    out = paged_decode_attention(
        case["q"], kp, vp, ks, vs, case["bt"], case["lengths"] + 1,
        fmt=fmt, n_kv_heads=case["KV"], mode=mode, window=case["window"],
        cap=case["cap"], impl=impl, interpret=interpret,
    )
    return out, kp, ks, vp, vs


MODES = ("rne", "rz", "stochastic")  # every mode core.quant.encode supports


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("fmt", ["e4m3", "e5m2", None])
def test_fused_write_attend_bit_identical_to_unfused(seed, fmt):
    kv_mode = MODES[seed % len(MODES)]
    mode = ("rne", "faithful")[seed % 2]
    case = _random_case(100 * seed + (0 if fmt is None else len(fmt)),
                        fmt=fmt)
    if kv_mode == "stochastic" and fmt is not None:
        stream = jax.random.PRNGKey(seed)
        fold = jax.vmap(jax.random.fold_in, in_axes=(None, 0))
        k_key = fold(jax.random.fold_in(stream, 0), case["lengths"])
        v_key = fold(jax.random.fold_in(stream, 1), case["lengths"])
    else:
        k_key = v_key = None
        if fmt is None:
            kv_mode = "rne"
    # interpret-mode Pallas is slow: exercise the kernel impl on a subset
    impls = ("ref", "batch") if seed % 3 else ("ref", "batch", "kernel")
    for impl in impls:
        interpret = True if impl == "kernel" else None
        fused = fused_decode_write_attend(
            case["q"], case["k_new"], case["v_new"], case["kp"], case["vp"],
            case["ks"], case["vs"], case["bt"], case["lengths"],
            fmt=fmt, n_kv_heads=case["KV"], mode=mode, kv_mode=kv_mode,
            k_key=k_key, v_key=v_key, write_mask=jnp.asarray(case["mask"]),
            window=case["window"], cap=case["cap"], impl=impl,
            interpret=interpret,
        )
        ref = _unfused(case, fmt=fmt, mode=mode, kv_mode=kv_mode,
                       k_key=k_key, v_key=v_key, impl=impl,
                       interpret=interpret)
        act = case["mask"]
        # attention output: identical on every active lane
        np.testing.assert_array_equal(
            np.asarray(fused[0])[act], np.asarray(ref[0])[act],
            err_msg=f"impl={impl} out",
        )
        # updated cache: identical on every real page (the null page's
        # contents are scatter-order-dependent and masked downstream)
        for i, name in ((1, "kp"), (2, "ks"), (3, "vp"), (4, "vs")):
            f, r = np.asarray(fused[i]), np.asarray(ref[i])
            np.testing.assert_array_equal(
                f[1:], r[1:], err_msg=f"impl={impl} {name}",
            )


def test_fused_masked_lanes_never_touch_real_pages():
    """A fully masked step must leave every real page bit-identical."""
    case = _random_case(7, fmt="e4m3")
    case["mask"] = np.zeros_like(case["mask"])
    out = fused_decode_write_attend(
        case["q"], case["k_new"], case["v_new"], case["kp"], case["vp"],
        case["ks"], case["vs"], case["bt"], case["lengths"],
        fmt="e4m3", n_kv_heads=case["KV"], kv_mode="rne",
        write_mask=jnp.asarray(case["mask"]), impl="batch",
    )
    np.testing.assert_array_equal(np.asarray(out[1])[1:],
                                  np.asarray(case["kp"])[1:])
    np.testing.assert_array_equal(np.asarray(out[3])[1:],
                                  np.asarray(case["vp"])[1:])
    np.testing.assert_array_equal(np.asarray(out[2])[1:],
                                  np.asarray(case["ks"])[1:])


@settings(max_examples=20, deadline=None)
@given(lengths=st.lists(st.integers(min_value=0, max_value=15),
                        min_size=2, max_size=2),
       mask=st.lists(st.booleans(), min_size=2, max_size=2),
       mode_i=st.integers(min_value=0, max_value=2))
def test_fused_equals_unfused_property(lengths, mask, mode_i):
    """Hypothesis sweep: fixed tiny geometry, arbitrary lengths/mask/mode."""
    if not any(mask):
        mask[0] = True
    case = _random_case(3, fmt="e4m3")
    # fixed geometry for this seed: B=?, clamp the drawn lengths to it
    B = case["lengths"].shape[0]
    maxlen = case["bt"].shape[1] * case["page"] - 1
    ls = np.resize(np.asarray(lengths), B).astype(np.int32) % (maxlen + 1)
    case["lengths"] = jnp.asarray(ls)
    case["mask"] = np.resize(np.asarray(mask, bool), B)
    if not case["mask"].any():
        case["mask"][0] = True
    kv_mode = MODES[mode_i]
    k_key = v_key = None
    if kv_mode == "stochastic":
        fold = jax.vmap(jax.random.fold_in, in_axes=(None, 0))
        k_key = fold(jax.random.PRNGKey(0), case["lengths"])
        v_key = fold(jax.random.PRNGKey(1), case["lengths"])
    fused = fused_decode_write_attend(
        case["q"], case["k_new"], case["v_new"], case["kp"], case["vp"],
        case["ks"], case["vs"], case["bt"], case["lengths"],
        fmt="e4m3", n_kv_heads=case["KV"], kv_mode=kv_mode,
        k_key=k_key, v_key=v_key, write_mask=jnp.asarray(case["mask"]),
        window=case["window"], cap=case["cap"], impl="batch",
    )
    ref = _unfused(case, fmt="e4m3", mode="rne", kv_mode=kv_mode,
                   k_key=k_key, v_key=v_key, impl="batch")
    act = case["mask"]
    np.testing.assert_array_equal(np.asarray(fused[0])[act],
                                  np.asarray(ref[0])[act])


# --------------------------------------------------------------------------- #
# Allocator op-sequence fuzz: invariants after EVERY op
# --------------------------------------------------------------------------- #
class _PoolDriver:
    """Random but precondition-respecting op generator over a PagePool.

    Tracks enough shadow state (spill records, registered keys) to only
    issue legal ops; pool exhaustion (RuntimeError) is a legal outcome
    for growth ops and is swallowed.
    """

    def __init__(self, rng, pool: PagePool):
        self.rng = rng
        self.pool = pool
        self.spills = {}  # slot -> (n_exclusive, pinned)
        self.seized = []
        self.n_keys = 0

    def _active_slots(self):
        return [s for s in range(self.pool.slots)
                if self.pool.pages_of[s] and s not in self.spills]

    def _empty_slots(self):
        return [s for s in range(self.pool.slots)
                if not self.pool.pages_of[s] and s not in self.spills]

    def op_grow(self):
        slots = [s for s in range(self.pool.slots) if s not in self.spills]
        slot = int(self.rng.choice(slots))
        n = int(self.rng.integers(1, 3))
        have = len(self.pool.pages_of[slot])
        if have + n > self.pool.max_pages_per_slot:
            return
        try:
            self.pool.alloc(slot, n)
        except RuntimeError:
            pass  # exhaustion is legal

    def op_grow_batch(self):
        tokens = np.zeros((self.pool.slots,), np.int64)
        for s in range(self.pool.slots):
            if s in self.spills:
                continue
            cap = self.pool.max_pages_per_slot * self.pool.page_size
            tokens[s] = int(self.rng.integers(0, cap + 1))
        try:
            self.pool.ensure_capacity_batch(tokens)
        except RuntimeError:
            pass

    def op_free(self):
        slots = self._active_slots()
        if not slots:
            return
        self.pool.free_slot(int(self.rng.choice(slots)))

    def op_register(self):
        slots = self._active_slots()
        if not slots:
            return
        slot = int(self.rng.choice(slots))
        pid = int(self.rng.choice(self.pool.pages_of[slot]))
        self.pool.register_prefix(f"key{self.n_keys}", pid)
        self.n_keys += 1

    def op_share(self):
        cached = [pid for pid in self.pool._page_key
                  if self.pool._pinned.get(pid, 0) == 0]
        slots = [s for s in range(self.pool.slots) if s not in self.spills
                 and len(self.pool.pages_of[s]) < self.pool.max_pages_per_slot]
        if not cached or not slots:
            return
        self.pool.share(int(self.rng.choice(slots)),
                        [int(self.rng.choice(cached))])

    def op_cow(self):
        # any slot holding a page it may not write (shared or registered)
        mask = self.pool.writable_mask()
        for slot in self._active_slots():
            owned = self.pool.pages_of[slot]
            bad = [i for i, pid in enumerate(owned) if not mask[pid]]
            if bad:
                try:
                    self.pool.cow_page(slot, int(self.rng.choice(bad)))
                except RuntimeError:
                    pass
                return

    def op_spill(self):
        slots = self._active_slots()
        if not slots:
            return
        slot = int(self.rng.choice(slots))
        spilled, pinned = self.pool.spill_slot(slot)
        self.spills[slot] = (len(spilled), pinned)

    def op_restore(self):
        if not self.spills:
            return
        slot = int(self.rng.choice(list(self.spills)))
        n, pinned = self.spills[slot]
        try:
            self.pool.restore_slot(slot, n, pinned)
        except RuntimeError:
            return  # not enough pages right now; retry another day
        del self.spills[slot]

    def op_unpin(self):
        if not self.spills:
            return
        slot = int(self.rng.choice(list(self.spills)))
        _, pinned = self.spills.pop(slot)
        self.pool.unpin(pinned)

    def op_seize(self):
        ids = self.pool.seize(int(self.rng.integers(1, 3)))
        self.seized.extend(ids)

    def op_release_seized(self):
        if not self.seized:
            return
        self.pool.release_seized([self.seized.pop()])

    def step(self):
        ops = [self.op_grow, self.op_grow, self.op_grow_batch, self.op_free,
               self.op_register, self.op_share, self.op_cow, self.op_spill,
               self.op_restore, self.op_unpin, self.op_seize,
               self.op_release_seized]
        self.rng.choice(ops)()


@pytest.mark.parametrize("seed", range(3))
def test_pool_op_sequence_fuzz(seed):
    rng = np.random.default_rng(seed)
    pool = PagePool(num_pages=17, page_size=4, slots=4, max_pages_per_slot=4)
    drv = _PoolDriver(rng, pool)
    for i in range(250):
        version_before = pool.version
        tables_before = pool.block_tables.copy()
        drv.step()
        pool.assert_invariants()
        # version-counter contract: any block-table change bumps it, so
        # the engine's cached device copy can never serve a stale table
        if not np.array_equal(tables_before, pool.block_tables):
            assert pool.version != version_before, f"stale version at op {i}"
        # writable_mask agrees with the scalar predicate everywhere
        mask = pool.writable_mask()
        for pid in range(pool.num_pages):
            assert bool(mask[pid]) == pool.writable(pid), f"pid {pid}"


def test_ensure_capacity_batch_matches_scalar_loop():
    """Differential: the batched allocator makes exactly the per-slot
    loop's decisions (same page ids, same order, same eviction)."""
    def fill(pool):
        rng = np.random.default_rng(42)
        pool.alloc(0, 2)
        pool.alloc(2, 1)
        pool.register_prefix("k0", pool.pages_of[0][0])
        pool.free_slot(0)  # parks the registered page in the LRU
        return rng

    a = PagePool(num_pages=11, page_size=4, slots=3, max_pages_per_slot=4)
    b = PagePool(num_pages=11, page_size=4, slots=3, max_pages_per_slot=4)
    fill(a)
    fill(b)
    for tokens in ([5, 0, 9], [13, 4, 12], [16, 16, 0]):
        try:
            a.ensure_capacity_batch(np.asarray(tokens))
            a_raised = None
        except RuntimeError as e:
            a_raised = str(e)
        b_raised = None
        try:
            for slot, t in enumerate(tokens):
                if t > 0:
                    b.ensure_capacity(slot, t)
        except RuntimeError as e:
            b_raised = str(e)
        assert (a_raised is None) == (b_raised is None)
        if a_raised is None:
            assert a.pages_of == b.pages_of
            np.testing.assert_array_equal(a.block_tables, b.block_tables)
            np.testing.assert_array_equal(a.ref, b.ref)
            assert a._free == b._free
        a.assert_invariants()
        b.assert_invariants()


def test_ensure_capacity_batch_is_one_version_bump():
    pool = PagePool(num_pages=9, page_size=4, slots=2, max_pages_per_slot=4)
    v0 = pool.version
    pool.ensure_capacity_batch(np.asarray([9, 5]))  # 3 + 2 pages
    assert pool.version == v0 + 1
    pool.ensure_capacity_batch(np.asarray([9, 5]))  # already satisfied
    assert pool.version == v0 + 1
