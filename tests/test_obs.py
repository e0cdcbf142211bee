"""Program spans (``repro.serving.obs``): the recorder's arithmetic, the
spans of a paged engine's admission and step, and their agreement with the
profiler's host plane."""
import glob
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs.spaceverse_pair import proxy_pair
from repro.core import eo_adapter as EO
from repro.core.cascade import TierModel
from repro.data import synthetic
from repro.serving import EngineCore, EngineCoreConfig, Request, obs

ADMIT = ["engine.admit", "engine.admit.lookup", "engine.admit.prefill",
         "engine.admit.pack", "engine.admit.dispatch", "engine.admit.record"]
STEP = ["engine.step", "engine.step.upload", "engine.step.dispatch",
        "engine.step.fetch", "engine.step.commit"]


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------

def test_parent_ids_follow_the_open_span():
    tr = obs.Tracer()
    with tr.span("a") as a:
        with tr.span("b") as b:
            with tr.span("c"):
                pass
        with tr.span("d"):
            pass
    with tr.span("e"):
        pass
    by = {s.name: s for s in tr.spans()}
    assert by["a"].parent is None and by["e"].parent is None
    assert by["b"].parent == a.id and by["d"].parent == a.id
    assert by["c"].parent == b.id
    # the ring holds spans in the order they closed
    assert [s.name for s in tr.spans()] == ["c", "b", "d", "a", "e"]


def test_stacks_are_per_thread():
    tr = obs.Tracer()
    with tr.span("main"):
        t = threading.Thread(target=lambda: tr.span("other").__enter__()
                             .__exit__(None, None, None))
        t.start()
        t.join()
    by = {s.name: s for s in tr.spans()}
    assert by["other"].parent is None


def test_attrs_set_while_open_and_totals():
    tr = obs.Tracer()
    for i in range(3):
        with tr.span("x", n=i) as sp:
            sp.attrs["done"] = True
    xs = tr.spans("x")
    assert [s.attrs for s in xs] == [{"n": i, "done": True} for i in range(3)]
    n, sec = tr.totals()["x"]
    assert n == 3
    assert sec == pytest.approx(sum(s.seconds for s in xs), abs=1e-12)


def test_self_time_is_duration_less_children():
    tr = obs.Tracer()
    with tr.span("p"):
        time.sleep(0.002)
        with tr.span("k1"):
            time.sleep(0.003)
        with tr.span("k2"):
            with tr.span("grandchild"):
                time.sleep(0.001)
    p = tr.spans("p")[0]
    kids = tr.spans("k1") + tr.spans("k2")
    assert tr.self_time(p) == pytest.approx(
        p.seconds - sum(k.seconds for k in kids), abs=1e-9)
    assert tr.self_time(p, among=kids) == pytest.approx(tr.self_time(p))
    assert tr.self_time(p, among=[]) == pytest.approx(p.seconds)
    assert 0.0 < tr.self_time(p) < p.seconds
    g = tr.spans("grandchild")[0]
    assert tr.self_time(g) == pytest.approx(g.seconds)


def test_window_filter_and_name():
    tr = obs.Tracer()
    with tr.span("early"):
        pass
    t_a = time.perf_counter()
    with tr.span("in"):
        with tr.span("in.child"):
            pass
    t_b = time.perf_counter()
    with tr.span("late"):
        pass
    assert [s.name for s in tr.spans(since=t_a, until=t_b)] == \
        ["in.child", "in"]
    assert [s.name for s in tr.spans("in", since=t_a)] == ["in"]
    assert [s.name for s in tr.spans(until=t_a)] == ["early"]
    assert tr.spans("late", until=t_b) == []


def test_ring_keeps_the_newest_spans():
    tr = obs.Tracer(maxlen=4)
    for i in range(10):
        with tr.span("s", i=i):
            pass
    assert [s.attrs["i"] for s in tr.spans()] == [6, 7, 8, 9]
    assert tr.totals()["s"][0] == 10            # totals outlive the ring


# ---------------------------------------------------------------------------
# the engine's spans
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sat_system():
    sat_cfg, _ = proxy_pair("small")
    ac = EO.EOAdapterConfig()
    params = EO.init_adapter(jax.random.PRNGKey(0), sat_cfg, ac)
    eo_cfg = synthetic.EOTaskConfig(image_size=ac.image_size, grid=ac.grid,
                                    num_classes=ac.num_classes)
    data = synthetic.make_dataset("cls", 8, seed=0, cfg=eo_cfg)
    return params, sat_cfg, ac, data


def _core(sat_system, **kw):
    params, cfg, ac, _ = sat_system
    return EngineCore(TierModel(params, cfg), ac,
                      EngineCoreConfig(slots=4, answer_vocab=9, **kw))


def _one_round(core, images):
    """One admission (two queries per image) and one step; returns the
    spans they recorded in start order, and what the engine said."""
    reqs = [Request(task=t, image=img, prompt=0)
            for img in images for t in ("vqa", "det")]
    t = time.perf_counter()
    m0 = core.stats["prefix_misses"]
    core.admit_many(reqs)
    rows = core.active_count()
    core.step()
    spans = sorted(obs.TRACER.spans(since=t), key=lambda s: s.id)
    return spans, reqs, rows, core.stats["prefix_misses"] - m0


def _walked_pages(core):
    """Pages the next step's paged decode reads, from the device's own
    cache indices: an active row at index i reads i + 1 positions."""
    ps = core._page_size
    return int(sum(-(-(int(i) + 1) // ps)
                   for i, s in zip(np.asarray(core._slot_index), core._slots)
                   if s.active))


def test_step_kv_pages_counts_the_pages_each_row_walks(sat_system):
    """``engine.step``'s ``kv_pages`` (host state only) equals the pages
    the step's decode walks by the device's cache indices, summed over the
    active rows, as rows join, grow and finish."""
    core = _core(sat_system)
    images = sat_system[3]["images"]
    core.admit_many([Request(task=t, image=images[0], prompt=0)
                     for t in ("det", "vqa")])
    seen = []
    for i in range(6):
        if i == 2:
            core.admit_many([Request(task="det", image=images[1], prompt=0)])
        want = _walked_pages(core)
        t = time.perf_counter()
        core.step()
        (step,) = obs.TRACER.spans("engine.step", since=t)
        assert step.attrs["kv_pages"] == want
        seen.append(want)
    assert len(set(seen)) > 1                   # rows joined and grew


def test_paged_admit_and_step_spans(sat_system):
    core = _core(sat_system)
    data = sat_system[3]
    spans, reqs, rows, misses = _one_round(core, data["images"][:2])
    assert [s.name for s in spans] == ADMIT + STEP
    admit, step = spans[0], spans[len(ADMIT)]
    assert admit.parent is None and step.parent is None
    assert all(s.parent == admit.id for s in spans[1:len(ADMIT)])
    assert all(s.parent == step.id for s in spans[len(ADMIT) + 1:])
    a = admit.attrs
    assert a["requests"] == 4 and a["bucket"] == 4
    assert a["request_ids"] == [r.request_id for r in reqs]
    assert a["misses"] == misses == 2
    assert a["active_after"] == 4
    assert a["tp"] == 1 and a["allreduce_bytes"] == 0      # one device
    assert spans[2].attrs["scenes"] == 2
    assert spans[2].attrs["image_bytes"] == 2 * data["images"][0].nbytes
    # every row was admitted just now: its step reads regions + prompt +
    # the token it writes
    fresh = -(-(core.ac.n_regions + 2) // core._page_size)
    assert step.attrs == {"slots": 4, "rows": rows,
                          "kv_pages": rows * fresh, "tp": 1,
                          "allreduce_bytes": 0}
    # the admission uploaded the block table; the step, the active mask
    assert spans[len(ADMIT) + 1].attrs["what"] == ("active",)
    # the vqa answers (one token) finish in this step, the det ones do not
    assert spans[-1].attrs["finished"] == [reqs[0].request_id,
                                           reqs[2].request_id]

    # a second round on a resident scene: no prefill
    spans, _, rows, misses = _one_round(core, data["images"][:1])
    names = [s.name for s in spans]
    assert names == [n for n in ADMIT + STEP if n != "engine.admit.prefill"]
    assert spans[0].attrs["misses"] == misses == 0
    assert spans[len(ADMIT) - 1].attrs["rows"] == rows == 4
    # a step right after a release re-uploads the block table too
    t = time.perf_counter()
    core.step()
    up = obs.TRACER.spans("engine.step.upload", since=t)
    assert [s.attrs["what"] for s in up] == [("active", "block_table")]


#: Qwen2-VL-7B's head ratios at a tiny width: 28 query and 4 KV heads, so
#: a (1, 4) mesh splits attention (one KV head a device) and the MLP
GS_TINY = dict(num_layers=2, d_model=64, num_heads=28, num_kv_heads=4,
               d_ff=128, head_dim=16, mrope_sections=(2, 3, 3),
               vocab_size=256, num_patches=16, tie_embeddings=False)


@pytest.mark.parametrize("model,split", [("gs-ratios", (True, True)),
                                         ("sat-proxy", (False, True))])
def test_mesh_spans_carry_tp_and_allreduce_bytes(sat_system, make_mesh,
                                                 model, split):
    """On a (1, 4) mesh ``engine.admit`` and ``engine.step`` carry the
    model-axis size and the bytes each device all-reduces in the call:
    (attention hook if attention is head-sharded, plus MLP hook if the MLP
    is) × layers × tokens the call runs × d_model × activation bytes.  The
    7B's ratios shard both; the satellite proxy's 2 KV heads do not divide
    by 4, so only its MLP all-reduces."""
    import dataclasses
    from repro.configs.spaceverse_pair import GS_CONFIG
    from repro.distributed import sharding as SH
    _, sat_cfg, ac, data = sat_system
    cfg = (dataclasses.replace(GS_CONFIG, **GS_TINY) if model == "gs-ratios"
           else sat_cfg)
    mesh = make_mesh(1, 4)
    plan = SH.tp_serving_plan(cfg, mesh)
    assert (plan.attn, plan.mlp) == split
    params = EO.init_adapter(jax.random.PRNGKey(0), cfg, ac)
    core = EngineCore(TierModel(params, cfg), ac,
                      EngineCoreConfig(slots=4, answer_vocab=9, mesh=mesh))
    spans, _, _, misses = _one_round(core, data["images"][:2])
    admit = next(s for s in spans if s.name == "engine.admit")
    step = next(s for s in spans if s.name == "engine.step")
    per_token = ((plan.attn + plan.mlp) * cfg.num_layers * cfg.d_model
                 * np.dtype(cfg.dtype).itemsize)
    assert misses == 2
    assert admit.attrs["tp"] == step.attrs["tp"] == 4
    # two scenes' region prefill, then the prompt rows in one step over
    # the whole 4-slot table
    assert admit.attrs["allreduce_bytes"] == per_token * (
        2 * ac.n_regions + 4)
    assert step.attrs["allreduce_bytes"] == per_token * 4


def test_dense_admit_and_step_spans(sat_system):
    core = _core(sat_system, cache_impl="dense")
    spans, _, rows, misses = _one_round(core, sat_system[3]["images"][:1])
    assert [s.name for s in spans] == [
        "engine.admit", "engine.admit.record", "engine.step",
        "engine.step.upload", "engine.step.dispatch", "engine.step.fetch",
        "engine.step.commit"]
    assert spans[0].attrs["misses"] == misses == 0
    assert spans[2].attrs["rows"] == rows == 2
    assert spans[3].attrs["what"] == ("active",)


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            out += [(e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for line in plane.lines for e in line.events
                    if e.name.startswith(("engine.", "bench."))]
    return sorted(out, key=lambda e: e[1])


def test_spans_land_on_the_profiler_host_plane(sat_system, tmp_path):
    core = _core(sat_system)
    images = sat_system[3]["images"]
    _one_round(core, images[:1])                 # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        spans, *_ = _one_round(core, images[1:2])
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    assert [e[0] for e in events] == [s.name for s in spans]
    assert not any(s.name.startswith("bench.") for s in spans)
    offsets = []
    for (name, t0, t1), s in zip(events, spans):
        assert t1 - t0 == pytest.approx(s.seconds, abs=1e-3), name
        offsets.append(t0 - s.t0)
    assert max(offsets) - min(offsets) < 1e-3
