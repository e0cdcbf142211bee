"""Sharded serving: mesh engines must reproduce the single-device oracle.

The contract under test is the tentpole's acceptance bar: for every
engine flavour (plain paged decode, speculative, chunked prefill, int8
pages) and every mesh shape dp×tp ∈ {1×2, 2×1, 2×2}, the sharded engine
emits token-for-token identical streams to the single-device core, with
ZERO steady-state recompiles (the CompileGuard raises under pytest), and
per-device KV footprint shrunk by the attention-sharding degree.  Plus
the DP isolation properties: per-shard page pools never share page ids,
and the router's per-shard accounting adds up.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

pytestmark = pytest.mark.mesh

from repro.configs.spaceverse_pair import proxy_pair
from repro.core import eo_adapter as EO
from repro.core.cascade import TierModel
from repro.data import synthetic
from repro.distributed import sharding as SH
from repro.serving import (EngineCore, EngineCoreConfig,
                           ShardedEngineCore, make_engine_core)
from repro.serving.request import Request


@pytest.fixture(scope="module")
def sharded_system():
    sat_cfg, _ = proxy_pair("small")
    ac = EO.EOAdapterConfig()
    params = EO.init_adapter(jax.random.PRNGKey(0), sat_cfg, ac)
    dparams = EO.init_adapter(jax.random.PRNGKey(1), sat_cfg, ac)
    eo_cfg = synthetic.EOTaskConfig(image_size=ac.image_size, grid=ac.grid,
                                    num_classes=ac.num_classes)
    data = synthetic.make_dataset("cls", 16, seed=0, cfg=eo_cfg)
    reqs = [Request(task="det", image=data["images"][0], prompt=0)]
    reqs += [Request(task="vqa", image=data["images"][i],
                     prompt=int(data["prompts"][i]) % 2)
             for i in range(1, 6)]
    return dict(cfg=sat_cfg, ac=ac, params=params, dparams=dparams,
                reqs=reqs)


FLAVOURS = {
    "plain": {},
    "spec": {"spec_gamma": 2},
    "chunked": {"prefill_chunk": 4},
    "int8": {"kv_dtype": "int8"},
}
SHAPES = [(1, 2), (2, 1), (2, 2)]


def _build(sys_, mesh, **kw):
    draft = (TierModel(sys_["dparams"], sys_["cfg"])
             if kw.get("spec_gamma") else None)
    return make_engine_core(
        TierModel(sys_["params"], sys_["cfg"]), sys_["ac"],
        EngineCoreConfig(slots=4, answer_vocab=9, mesh=mesh, **kw),
        draft=draft)


def _drive(core, reqs):
    core.warmup()
    outs = {}
    queue = list(reqs)
    while queue or core.active_count():
        k = min(len(queue), len(core.free_slots()))
        if k:
            core.admit_many(queue[:k])
            queue = queue[k:]
        for req, toks in core.step():
            outs[req.request_id] = np.asarray(toks).tolist()
    return outs


_REF_CACHE = {}


def _reference(sys_, flavour):
    if flavour not in _REF_CACHE:
        _REF_CACHE[flavour] = _drive(_build(sys_, None,
                                            **FLAVOURS[flavour]),
                                     sys_["reqs"])
    return _REF_CACHE[flavour]


@pytest.mark.parametrize("flavour", sorted(FLAVOURS))
@pytest.mark.parametrize("dp,tp", SHAPES,
                         ids=[f"dp{d}tp{t}" for d, t in SHAPES])
def test_sharded_matches_single_device(sharded_system, make_mesh,
                                       flavour, dp, tp):
    sys_ = sharded_system
    core = _build(sys_, make_mesh(dp, tp), **FLAVOURS[flavour])
    assert isinstance(core,
                      ShardedEngineCore if dp > 1 else EngineCore)
    got = _drive(core, sys_["reqs"])
    assert got == _reference(sys_, flavour)
    sch = core.scheduler_stats()
    assert sch["steady_recompiles"] == 0
    ks = core.kv_stats()
    if tp > 1:
        # per-device pools hold only this shard's KV heads
        assert ks["kv_bytes_per_slot_device"] * tp == ks["kv_bytes_per_slot"]
    if dp > 1:
        per = ks["per_shard"]
        assert len(per) == dp
        assert sum(r["slots"] for r in per) == 4
        assert sum(r["routed"] for r in per) == len(sys_["reqs"])
        assert sch["per_shard"] == per


def test_per_shard_pools_disjoint(sharded_system, make_mesh):
    """DP shards own private page allocators: page ids overlap numerically
    (each pool numbers its own pages) but the objects, accounting and
    prefix caches are fully independent — churn on one shard never moves
    the other's pages."""
    sys_ = sharded_system
    core = _build(sys_, make_mesh(2, 1))
    a, b = core.shards
    assert a._pool is not b._pool
    assert a._prefix is not b._prefix
    core.warmup()
    core.admit_many(sys_["reqs"][:2])   # routed across both shards
    used_a, used_b = a._pool.pages_in_use, b._pool.pages_in_use
    assert used_a > 0 and used_b > 0
    # drain shard a only by finishing its requests
    while a.active_count():
        core.step()
    assert b._pool.pages_in_use == used_b or b.active_count() == 0
    # a's slot freed its private pages; b's accounting never moved mid-run
    total = a._pool.pages_in_use + b._pool.pages_in_use
    assert total <= used_a + used_b


def test_scene_affinity_routing(sharded_system, make_mesh):
    """Fan-out over one scene routes to the shard already holding its
    prefix pages — the prefix-cache hit rate survives the DP split."""
    sys_ = sharded_system
    core = _build(sys_, make_mesh(2, 1))
    core.warmup()
    img = sys_["reqs"][1].image
    fanout = [Request(task="vqa", image=img, prompt=p % 2)
              for p in range(4)]
    # sequential arrivals: after the first finishes, its scene's pages
    # stay resident on ONE shard — later arrivals must follow them there
    for r in fanout:
        outs = _drive(core, [r])
        assert len(outs) == 1
    ks = core.kv_stats()
    # 1 miss (first admission), 3 affinity-routed hits — all on one shard
    assert ks["prefix_hit_rate"] == pytest.approx(0.75)
    assert max(r["routed"] for r in ks["per_shard"]) == 4


def test_mesh_validation_errors(sharded_system, make_mesh):
    sys_ = sharded_system
    mesh = make_mesh(2, 2)
    with pytest.raises(ValueError, match="'data' axis"):
        # EngineCore refuses a non-trivial data axis
        EngineCore(TierModel(sys_["params"], sys_["cfg"]), sys_["ac"],
                   EngineCoreConfig(slots=4, answer_vocab=9, mesh=mesh))
    with pytest.raises(ValueError, match="mesh"):
        ShardedEngineCore(TierModel(sys_["params"], sys_["cfg"]),
                          sys_["ac"],
                          EngineCoreConfig(slots=4, answer_vocab=9))
    with pytest.raises(ValueError, match="slots"):
        ShardedEngineCore(TierModel(sys_["params"], sys_["cfg"]),
                          sys_["ac"],
                          EngineCoreConfig(slots=1, answer_vocab=9,
                                           mesh=mesh))


@pytest.mark.parametrize("dp,tp", [(1, 2), (2, 2)],
                         ids=["dp1tp2", "dp2tp2"])
def test_batch_path_matches_single_device(sharded_system, make_mesh, dp, tp):
    """``generate``/``prefill``/``decode_chunk`` (the cascade executor's
    satellite tier) run tensor-parallel on a mesh engine too, and agree with
    the single-device engine: same greedy tokens, same distributions."""
    sys_ = sharded_system
    imgs = jnp.stack([jnp.asarray(r.image) for r in sys_["reqs"][:3]])
    prompts = jnp.asarray([1, 0, 1], jnp.int32)
    ref, core = _build(sys_, None), _build(sys_, make_mesh(dp, tp))
    for task in ("vqa", "det"):
        want = ref.generate(task, imgs, prompts, 9)
        got = core.generate(task, imgs, prompts, 9)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("tp", [2, 4])
def test_init_sharded_matches_unsharded_init(sharded_system, make_mesh, tp):
    """``SH.init_sharded`` makes the same weights as the jitted plain init
    (bitwise: the random stream is partitionable; eager op-by-op init may
    round a scaled normal differently by an ulp), each leaf already placed
    at its serving-plan sharding."""
    sys_ = sharded_system
    cfg, ac, mesh = sys_["cfg"], sys_["ac"], make_mesh(1, tp)

    def init(key):
        return EO.init_adapter(key, cfg, ac)

    key = jax.random.PRNGKey(0)
    got = SH.init_sharded(init, cfg, mesh, key)
    specs = SH.adapter_param_specs(SH.tp_serving_plan(cfg, mesh),
                                   jax.eval_shape(init, key))
    sharded = 0
    for g, w, s in zip(jax.tree.leaves(got),
                       jax.tree.leaves(jax.jit(init)(key)),
                       jax.tree.leaves(specs, is_leaf=lambda x:
                                       isinstance(x, P))):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        assert g.sharding.is_equivalent_to(NamedSharding(mesh, s), g.ndim)
        sharded += g.addressable_shards[0].data.size < g.size
    assert sharded > 0          # the plan split some weights over "model"


def test_factory_picks_engine(sharded_system, make_mesh):
    sys_ = sharded_system
    assert isinstance(_build(sys_, None), EngineCore)
    assert isinstance(_build(sys_, make_mesh(1, 2)), EngineCore)
    assert isinstance(_build(sys_, make_mesh(2, 1)), ShardedEngineCore)
