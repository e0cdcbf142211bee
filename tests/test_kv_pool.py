"""Paged KV cache: allocator invariants + engine-level prefix sharing.

Three layers of guarantees:

- allocator: alloc/free/ref-count round-trips, no double-free, the trash
  page is never handed out, LRU eviction only touches zero-user prefixes
  (hypothesis-based state-machine sweep where hypothesis is available);
- engine: the paged cache serves a mixed-task slot table token-for-token
  identically to the dense oracle (``EngineCoreConfig(cache_impl="dense")``),
  scene fan-out shares prefix pages (hit rate > 0, fewer prefilled tokens)
  and **shared prefix pages are never written after sharing**;
- accounting: page refcounts return to the cache-only state after the
  queue drains, and kv_stats reports an amortised per-slot footprint below
  the dense reservation under fan-out.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.spaceverse_pair import proxy_pair
from repro.core import eo_adapter as EO
from repro.core.cascade import TierModel
from repro.data import synthetic
from repro.models import transformer as T
from repro.serving import (EngineConfig, EngineCore, EngineCoreConfig,
                           InferenceEngine, KVPagePool, Request)
from repro.serving.kv_pool import PrefixCache, TRASH_PAGE, page_nbytes


# ---------------------------------------------------------------------------
# allocator invariants
# ---------------------------------------------------------------------------

def test_pool_hands_a_freed_run_out_again_in_order():
    """Pages come off the free list in the order they were freed, so a
    slot's consecutive private pages (or a scene's prefix pages) are
    consecutive again when reused: the paged decode kernel copies such a
    run in one piece."""
    pool = KVPagePool(n_pages=12, page_size=4)
    a = pool.alloc(4)
    b = pool.alloc(3)
    assert a == [1, 2, 3, 4] and b == [5, 6, 7]
    pool.free(a)
    pool.free(b)
    assert pool.alloc(3) == b
    assert pool.alloc(4) == a
    # a failed free still returns what it released before the fault
    pool.free(a[:2])
    with pytest.raises(ValueError):
        pool.free([a[2], a[0]])
    assert sorted(pool.alloc(3)) == a[:3]


def test_pool_alloc_free_roundtrip():
    pool = KVPagePool(n_pages=9, page_size=4)
    assert pool.free_pages == 8                 # page 0 reserved as trash
    a = pool.alloc(3)
    b = pool.alloc(2)
    assert len(set(a) | set(b)) == 5 and TRASH_PAGE not in a + b
    assert pool.pages_in_use == 5
    pool.free(a)
    assert pool.free_pages == 6
    c = pool.alloc(6)
    assert pool.free_pages == 0
    with pytest.raises(MemoryError):
        pool.alloc(1)
    pool.free(b)
    pool.free(c)
    assert pool.free_pages == 8 and pool.pages_in_use == 0


def test_pool_refcounts_and_double_free():
    pool = KVPagePool(n_pages=5, page_size=4)
    (p,) = pool.alloc(1)
    pool.incref([p])
    pool.incref([p])
    assert pool.refcount(p) == 3
    pool.free([p])
    pool.free([p])
    assert pool.refcount(p) == 1 and pool.free_pages == 3   # still held
    pool.free([p])
    assert pool.free_pages == 4
    with pytest.raises(ValueError):
        pool.free([p])                                      # double free
    with pytest.raises(ValueError):
        pool.incref([p])                                    # not allocated


def test_pool_trash_page_is_sacred():
    pool = KVPagePool(n_pages=4, page_size=2)
    with pytest.raises(ValueError):
        pool.free([TRASH_PAGE])
    with pytest.raises(ValueError):
        pool.incref([TRASH_PAGE])
    assert TRASH_PAGE not in pool.alloc(3)


def test_prefix_cache_eviction_skips_in_use_entries():
    pool = KVPagePool(n_pages=7, page_size=4)
    cache = PrefixCache(pool, capacity=3)
    cache.put("a", pool.alloc(2), None)
    cache.put("b", pool.alloc(2), None)
    cache.acquire("a")                          # scene a has a live user
    cache.evict_for(need_pages=4)               # must evict b, not a
    assert "a" in cache and "b" not in cache
    assert pool.free_pages == 4
    with pytest.raises(MemoryError):
        cache.evict_for(need_pages=6)           # a is in use: can't evict
    cache.release("a")
    cache.evict_for(need_pages=6)
    assert len(cache) == 0 and pool.free_pages == 6


def test_pool_state_machine_hypothesis():
    """Randomised alloc/incref/free interleavings preserve the conservation
    invariant: free + in-use == n_pages - 1 and no page is ever both."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.given(st.lists(st.tuples(st.sampled_from(["alloc", "incref", "free"]),
                                  st.integers(0, 7)), max_size=60))
    @hyp.settings(deadline=None, max_examples=60)
    def run(ops):
        pool = KVPagePool(n_pages=9, page_size=4)
        held = []                               # (page, refs_we_hold)
        for op, arg in ops:
            if op == "alloc":
                n = arg % 3
                if n <= pool.free_pages:
                    held.extend((p, 1) for p in pool.alloc(n))
                else:
                    with pytest.raises(MemoryError):
                        pool.alloc(n)
            elif op == "incref" and held:
                i = arg % len(held)
                p, r = held[i]
                pool.incref([p])
                held[i] = (p, r + 1)
            elif op == "free" and held:
                i = arg % len(held)
                p, r = held[i]
                pool.free([p])
                held[i] = (p, r - 1)
                if r - 1 == 0:
                    held.pop(i)
            live = {p for p, _ in held}
            assert pool.pages_in_use == len(live)
            assert pool.free_pages == pool.n_pages - 1 - len(live)
            for p, r in held:
                assert pool.refcount(p) == r
        # full teardown: everything refcounted frees cleanly exactly once
        for p, r in held:
            pool.free([p] * r)
        assert pool.free_pages == pool.n_pages - 1

    run()


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_overload_state_machine_hypothesis(kv_dtype):
    """Randomised admit/preempt/re-admit/reject/finish interleavings over
    the pool + prefix cache, following the overload layer's
    check-then-commit discipline (ISSUE 7): an admission runs only when the
    pure headroom probe (``free + evictable_pages(protect)``) says it fits,
    a rejection touches nothing, and preemption is drop-and-recompute
    (private pages freed, prefix released, scene parked for re-admission).
    After every action: pages_in_use == private + shared, per-scene users
    match the model, shared pages hold 1 + users references, and the trash
    page is never allocated.

    The pool is sized from ONE device-byte budget through
    ``page_nbytes(kv_dtype=...)`` — the int8 variant runs the same machine
    on the ~3.5× page count the same bytes buy, which is exactly the extra
    headroom the overload layer's admission probe sees in production."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    PRIV, SHARED, SLOTS, CAP = 2, 3, 3, 3
    budget = 17 * page_nbytes(4, 2, 32)            # 17 fp pages' worth
    n_pages = budget // page_nbytes(4, 2, 32, kv_dtype=kv_dtype)
    assert n_pages == 17 if kv_dtype is None else n_pages >= 2 * 17

    @hyp.given(st.lists(st.tuples(
        st.sampled_from(["admit", "preempt", "readmit", "finish"]),
        st.integers(0, 11)), max_size=80))
    @hyp.settings(deadline=None, max_examples=60)
    def run(ops):
        pool = KVPagePool(n_pages=n_pages, page_size=4)
        cache = PrefixCache(pool, capacity=CAP)
        active = []                             # (scene, private_pages)
        parked = []                             # queued / preempted scenes

        def fits(scene):
            if len(active) >= SLOTS:
                return False
            protect = {s for s, _ in active} | {scene}
            new = 0 if scene in cache else 1
            need = PRIV + new * SHARED
            if pool.free_pages + cache.evictable_pages(protect) < need:
                return False
            resident = len(cache) - cache.evictable_entries(protect)
            return resident + new <= cache.capacity

        def admit(scene):
            """Commit phase: by construction of ``fits`` this cannot raise
            (the admission-atomicity contract at the allocator layer)."""
            if not fits(scene):
                return False
            protect = {s for s, _ in active} | {scene}
            new = 0 if scene in cache else 1
            cache.evict_for(PRIV + new * SHARED, need_entries=new,
                            protect=protect)
            if scene not in cache:
                cache.put(scene, pool.alloc(SHARED), None)
            cache.acquire(scene)
            active.append((scene, pool.alloc(PRIV)))
            return True

        for op, arg in ops:
            if op == "admit":
                scene = f"s{arg % 5}"
                if not admit(scene):            # reject path: pure no-op
                    parked.append(scene)
            elif op == "preempt" and active:
                s_, pages = active.pop(arg % len(active))
                pool.free(pages)
                cache.release(s_)
                parked.append(s_)
            elif op == "readmit" and parked:
                s_ = parked.pop(arg % len(parked))
                if not admit(s_):
                    parked.append(s_)
            elif op == "finish" and active:
                s_, pages = active.pop(arg % len(active))
                pool.free(pages)
                cache.release(s_)
            # conservation after every action
            priv = sum(len(p) for _, p in active)
            shared = cache.stats()["shared_pages"]
            assert pool.pages_in_use == priv + shared
            users = {}
            for s_, _ in active:
                users[s_] = users.get(s_, 0) + 1
            assert {s_: e.users for s_, e in cache._entries.items()
                    if e.users} == users
            for s_, e in cache._entries.items():
                for p in e.pages:
                    assert p != TRASH_PAGE
                    assert pool.refcount(p) == 1 + e.users
        # drain: finish everything, pool returns to the cache-only state
        for s_, pages in active:
            pool.free(pages)
            cache.release(s_)
        assert pool.pages_in_use == cache.stats()["shared_pages"]
        assert cache.stats()["entries_in_use"] == 0

    run()


# ---------------------------------------------------------------------------
# engine level: paged vs dense equivalence + prefix sharing
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sat_system():
    sat_cfg, _ = proxy_pair("small")
    ac = EO.EOAdapterConfig()
    params = EO.init_adapter(jax.random.PRNGKey(0), sat_cfg, ac)
    eo_cfg = synthetic.EOTaskConfig(image_size=ac.image_size, grid=ac.grid,
                                    num_classes=ac.num_classes)
    data = synthetic.make_dataset("cls", 16, seed=0, cfg=eo_cfg)
    return params, sat_cfg, ac, data


def _fanout_queue(data, n_scenes=3, per_scene=3):
    """Scene fan-out: several queries (mixed tasks) over each captured
    scene — the paper's dominant traffic shape."""
    reqs = []
    for s in range(n_scenes):
        img = data["images"][s]
        reqs.append(Request(task="det", image=img, prompt=0))
        reqs += [Request(task="vqa", image=img, prompt=q % 2)
                 for q in range(per_scene - 2)]
        reqs.append(Request(task="cls", image=img, prompt=0))
    return reqs


def _serve(params, cfg, ac, reqs, cache_impl, slots=3):
    eng = InferenceEngine(params, cfg, ac,
                          EngineConfig(slots=slots, answer_vocab=9,
                                       cache_impl=cache_impl))
    resps = eng.serve(list(reqs))
    by_id = {r.request_id: np.asarray(r.tokens).tolist() for r in resps}
    return by_id, eng.core


def test_paged_matches_dense_token_for_token_mixed_tasks(sat_system):
    """The tentpole equivalence: the paged cache with shared prefix pages
    serves a mixed det/vqa/cls fan-out queue (mid-stream refills included)
    with exactly the token streams of the dense worst-case cache."""
    params, cfg, ac, data = sat_system
    reqs = _fanout_queue(data)
    toks_p, core_p = _serve(params, cfg, ac, reqs, "paged")
    toks_d, core_d = _serve(params, cfg, ac, reqs, "dense")
    assert toks_p == toks_d
    assert core_p.stats["finished"] == core_d.stats["finished"] == len(reqs)
    # sharing really happened, and it saved prefill work at equal outputs
    assert core_p.stats["prefix_hits"] > 0
    assert core_p.stats["prefix_misses"] == 3          # one per scene
    assert core_p.stats["prefill_tokens"] < core_d.stats["prefill_tokens"]


def test_paged_matches_vmap_oracle_token_for_token(sat_system):
    """Transitive closure with the PR-2 oracle: paged-batched equals the
    legacy per-slot vmap engine (which steps the dense layout)."""
    params, cfg, ac, data = sat_system
    reqs = _fanout_queue(data, n_scenes=2, per_scene=3)
    toks_p, _ = _serve(params, cfg, ac, reqs, "paged", slots=2)
    eng = InferenceEngine(params, cfg, ac,
                          EngineConfig(slots=2, answer_vocab=9,
                                       step_impl="vmap"))
    resps = eng.serve([Request(task=r.task, image=r.image, prompt=r.prompt,
                               request_id=r.request_id) for r in reqs])
    toks_v = {r.request_id: np.asarray(r.tokens).tolist() for r in resps}
    assert toks_p == toks_v


def _shared_page_snapshot(core):
    """Concatenated copy of every shared prefix page across all KV pools."""
    pages = sorted({p for e in core._prefix._entries.values()
                    for p in e.pages})
    assert pages, "no resident prefixes to snapshot"
    out = []
    T.map_cache_kinds(
        core.tier.cfg, [core._slot_cache],
        kv=lambda t: out.append(jax.tree.map(
            lambda x: np.asarray(x[:, pages]), t)),
        state=lambda t: None)
    return pages, out


def test_shared_prefix_pages_never_written_after_sharing(sat_system):
    """Read-only sharing is the core safety invariant: once a scene's
    prefix pages are resident, admissions and decode steps of requests
    mapping them must never modify their contents."""
    params, cfg, ac, data = sat_system
    core = EngineCore(TierModel(params, cfg), ac,
                      EngineCoreConfig(slots=3, answer_vocab=9))
    img = data["images"][0]
    core.admit_many([Request(task="det", image=img, prompt=0)])
    pages0, snap0 = _shared_page_snapshot(core)
    # fan more queries over the same scene while decoding the det answer
    core.admit_many([Request(task="vqa", image=img, prompt=0),
                     Request(task="cls", image=img, prompt=0)])
    for _ in range(4):
        core.step()
    pages1, snap1 = _shared_page_snapshot(core)
    assert pages1 == pages0
    for a, b in zip(jax.tree.leaves(snap0), jax.tree.leaves(snap1)):
        np.testing.assert_array_equal(a, b)


def test_paged_release_returns_pages_and_refcounts(sat_system):
    """After the queue drains, every private page is back in the free list
    and prefix pages hold exactly the cache's own reference."""
    params, cfg, ac, data = sat_system
    reqs = _fanout_queue(data, n_scenes=2, per_scene=3)
    _, core = _serve(params, cfg, ac, reqs, "paged", slots=3)
    assert core.active_count() == 0
    st = core._prefix.stats()
    assert st["entries_in_use"] == 0
    assert core._pool.pages_in_use == st["shared_pages"]
    for e in core._prefix._entries.values():
        assert all(core._pool.refcount(p) == 1 for p in e.pages)
    # inactive block-table rows all point at the trash page
    assert (core._bt_np == TRASH_PAGE).all()


def test_paged_prefix_eviction_under_pool_pressure(sat_system):
    """More distinct scenes than the prefix cache keeps resident: old
    zero-user prefixes evict, serving still completes, and the pool never
    double-books a page."""
    params, cfg, ac, data = sat_system
    eng = InferenceEngine(params, cfg, ac,
                          EngineConfig(slots=2, answer_vocab=9,
                                       prefix_cache_scenes=1))
    reqs = [Request(task="vqa", image=data["images"][i % 8], prompt=0)
            for i in range(10)]
    resps = eng.serve(reqs)
    assert len(resps) == 10
    core = eng.core
    assert len(core._prefix) <= core._prefix.capacity
    # evictions happened: more misses than resident entries
    assert core.stats["prefix_misses"] > len(core._prefix)


def test_eviction_never_touches_scenes_of_current_batch(sat_system):
    """Regression: a batch mixing a *hit* on the LRU resident scene with a
    *miss* that triggers eviction must not evict the hit scene before the
    batch acquires it (the admission protects its own scenes)."""
    params, cfg, ac, data = sat_system
    core = EngineCore(TierModel(params, cfg), ac,
                      EngineCoreConfig(slots=2, answer_vocab=9,
                                       prefix_cache_scenes=1))
    for s in range(3):                          # scenes 0,1,2 resident, idle
        core.admit_many([Request(task="vqa", image=data["images"][s],
                                 prompt=0, scene_id=s)])
        while core.active_count():
            core.step()
    # hit on LRU scene 0 + miss forcing eviction, in one batch
    core.admit_many([Request(task="vqa", image=data["images"][0], prompt=0,
                             scene_id=0),
                     Request(task="vqa", image=data["images"][7], prompt=0,
                             scene_id=7)])
    while core.active_count():
        core.step()
    assert core.stats["prefix_hits"] == 1
    assert len(core._prefix) <= core._prefix.capacity


def test_prefix_cache_protect_set():
    pool = KVPagePool(n_pages=7, page_size=4)
    cache = PrefixCache(pool, capacity=4)
    cache.put("a", pool.alloc(2), None)
    cache.put("b", pool.alloc(2), None)
    cache.evict_for(need_pages=4, protect={"a"})    # evicts b, spares LRU a
    assert "a" in cache and "b" not in cache
    with pytest.raises(MemoryError):
        cache.evict_for(need_pages=6, protect={"a"})


def test_paged_kv_footprint_beats_dense_under_fanout(sat_system):
    """Under scene fan-out the amortised per-slot KV bytes (private pages +
    shared prefix / users) drop below the dense worst-case reservation."""
    params, cfg, ac, data = sat_system
    slots = 4
    img = data["images"][0]
    core = EngineCore(TierModel(params, cfg), ac,
                      EngineCoreConfig(slots=slots, answer_vocab=9))
    core.admit_many([Request(task="det", image=img, prompt=0)
                     for _ in range(slots)])
    paged = core.kv_stats()
    dense = EngineCore(TierModel(params, cfg), ac,
                       EngineCoreConfig(slots=slots, answer_vocab=9,
                                        cache_impl="dense")).kv_stats()
    assert paged["prefix_hit_rate"] > 0
    assert paged["kv_bytes_per_slot"] < dense["kv_bytes_per_slot"]


def test_paged_page_size_clamps_to_prefix_divisor(sat_system):
    """A page size that doesn't divide N_r clamps to the largest common
    divisor (the shared prefix must occupy whole pages); non-positive sizes
    are rejected outright."""
    params, cfg, ac, _ = sat_system
    core = EngineCore(TierModel(params, cfg), ac,
                      EngineCoreConfig(slots=2, answer_vocab=9, page_size=7))
    assert core._page_size == 1                 # gcd(7, 16)
    assert ac.n_regions % core._page_size == 0
    with pytest.raises(ValueError):
        EngineCore(TierModel(params, cfg), ac,
                   EngineCoreConfig(slots=2, answer_vocab=9, page_size=0))


def test_scene_id_overrides_pixel_hash(sat_system):
    """An explicit scene_id groups requests even when producers hand over
    distinct (but same-capture) buffers, and distinct ids keep distinct
    scenes apart regardless of pixels."""
    params, cfg, ac, data = sat_system
    core = EngineCore(TierModel(params, cfg), ac,
                      EngineCoreConfig(slots=4, answer_vocab=9))
    img = data["images"][0]
    core.admit_many([
        Request(task="vqa", image=np.array(img), prompt=0, scene_id="s0"),
        Request(task="cls", image=np.array(img), prompt=0, scene_id="s0"),
        Request(task="vqa", image=np.array(img), prompt=0, scene_id="s1"),
    ])
    assert core.stats["prefix_misses"] == 2
    assert core.stats["prefix_hits"] == 1


def test_shared_core_keyed_by_config_value(sat_system):
    """The shared-core cache must key on config *value*, not ``id()`` —
    object ids are reused after garbage collection."""
    import gc
    from repro.serving.engine_core import shared_core
    params, cfg, ac, _ = sat_system
    tier = TierModel(params, cfg)
    core1 = shared_core(tier, EO.EOAdapterConfig())
    core2 = shared_core(tier, EO.EOAdapterConfig())          # equal value
    assert core1 is core2
    gc.collect()
    other = shared_core(tier, EO.EOAdapterConfig(grid=2, image_size=32))
    assert other is not core1
    assert shared_core(tier, EO.EOAdapterConfig()) is core1  # still resident


def test_admission_headroom_scales_with_kv_dtype(sat_system):
    """Satellite check for the int8 pool: under ONE ``pool_bytes`` budget
    the admission probe (``page_demand`` against the pool's free pages)
    sees ≥ 2× the admissible requests on the int8 engine — per-request
    page demand is dtype-independent (pages are the unit), the budget just
    buys ~3.5× the pages."""
    params, cfg, ac, data = sat_system
    mk = lambda dt, pb=None: EngineCore(
        TierModel(params, cfg), ac,
        EngineCoreConfig(slots=8, answer_vocab=9, pool_bytes=pb,
                         kv_dtype=dt))
    budget = mk(None)._page_nbytes_stack() * 24     # a 24-fp-page budget
    cores = {dt: mk(dt, budget) for dt in (None, "int8")}
    req = Request(task="det", image=data["images"][0], prompt=0,
                  scene_id="probe")
    demand = {dt: c.page_demand(req) for dt, c in cores.items()}
    assert demand[None] == demand["int8"]           # pages, not bytes
    cap = {dt: c._pool.free_pages // demand[dt] for dt, c in cores.items()}
    assert cap["int8"] >= 2 * cap[None] >= 2, cap
