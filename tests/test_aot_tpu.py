"""Sandbox pre-flight: does Mosaic/XLA compile our programs for a v5e?

The installed libtpu compiles for a TPU topology with no chip attached, so
"will it compile on the chip" is answerable here. ``runtime.on_tpu`` is
replaced so every ``auto`` knob resolves as it does on a TPU, the programs
are lowered against ``ShapeDtypeStruct``s placed on a ``v5e:2x2`` topology
device, and ``.compile()`` runs the real TPU compiler. Compile-only: this
says nothing about results, hangs or speed (``chip_smoke.py`` does, on the
chip). Interpreter parity never predicted these verdicts: the three
programs Mosaic refused (PR 21) passed it, and left the tree in PR 30.

libtpu takes ``/tmp/libtpu_lockfile``: one such process at a time.
"""
import importlib.util
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import lightgbm_tpu as lgb
from bench import make_higgs_like, make_mslr_like
from lightgbm_tpu import fused, runtime
from lightgbm_tpu.ops import partition

pytestmark = pytest.mark.slow

N_BIN, N_RANK = 200_000, 300_000      # F = 28 and 137
BASE = {"num_leaves": 255, "max_bin": 255, "verbosity": -1}


@pytest.fixture(scope="module")
def topo():
    try:
        from jax.experimental import topologies
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is locked by another process
        pytest.skip("no TPU topology can be made here: %s: %s"
                    % (type(e).__name__, str(e).splitlines()[0][:200]))
    assert "v5" in t.devices[0].device_kind.lower(), t.devices[0].device_kind
    return t


@pytest.fixture(autouse=True)
def as_on_tpu(monkeypatch):
    """Resolve and trace as a TPU does. jit's trace caches do not key on
    the patched function, so they are dropped on the way in and out."""
    assert not partition._INTERPRET
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    jax.clear_caches()
    fused._BLOCK_CACHE.clear()
    yield
    jax.clear_caches()
    fused._BLOCK_CACHE.clear()


@pytest.fixture(scope="module")
def ds_binary():
    X, y = make_higgs_like(N_BIN)
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    return ds


@pytest.fixture(scope="module")
def ds_rank():
    X, y, group = make_mslr_like(N_RANK)
    ds = lgb.Dataset(X, label=y, group=group)
    ds.construct()
    return ds


@pytest.fixture(scope="module")
def ds_onehot():
    """The expo.train cell's table at 200K rows: 700 one-hot CSR columns
    that EFB bundles into 10 device columns, so W = 32 planes."""
    data, cfg = _bench_table("expo-binary-255", N_BIN)
    ds = lgb.Dataset(data["X"], label=data["label"], params=cfg["params"])
    ds.construct()
    return ds


def _bench_table(config, rows, seed=11):
    """A benchmark configuration's own table at ``rows`` rows -> (data, cfg)."""
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    with open(os.path.join(bench, "configs", config + ".json")) as f:
        cfg = json.load(f)
    spec = importlib.util.spec_from_file_location(
        "bench_datagen_" + cfg["datagen"]["kind"],
        os.path.join(bench, "datagen", cfg["datagen"]["kind"] + ".py"))
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    return gen.make(dict(cfg["shape"], rows=rows), cfg["datagen"]["args"],
                    seed), cfg


@pytest.fixture(scope="module")
def ds_coded():
    """The expo_cat.train cell's table at 200K rows: expo's eight source
    columns as dense codes, six of them ``categorical_feature``."""
    data, cfg = _bench_table("expo-categorical-255", N_BIN)
    ds = lgb.Dataset(data["X"], label=data["label"], params=cfg["params"])
    ds.construct()
    return ds


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.asarray(a).dtype,
                                       sharding=sharding), tree)


def compile_block(topo, ds, params, k=5):
    """AOT-compile FusedTrainer's k-iteration block; returns (resolved
    build kwargs, Compiled)."""
    g = lgb.Booster(dict(BASE, **params), ds).inner
    assert g.supports_fused()
    ft = fused.FusedTrainer(g)
    args = (g.train_score.score, ft._used_dev(), g._key, jnp.int32(0),
            g.learner.bins, g.learner.meta,
            fused._obj_array_state(g.objective))
    sds = _abstract(args, SingleDeviceSharding(topo.devices[0]))
    return g.learner.build_kwargs(), ft._block_fn(k).lower(*sds).compile()


def resolved(kw):
    return kw["work_layout"], kw["part_kernel"], kw["hist_kernel"]


# ------------------------------------------------- what auto picks on a TPU

def test_default_binary_block_compiles(topo, ds_binary):
    kw, c = compile_block(topo, ds_binary, {"objective": "binary"})
    assert resolved(kw) == ("planes", "pallas", "pallas")
    assert c.memory_analysis().temp_size_in_bytes > 0
    # the block really holds the kernel auto resolved to
    assert "hist_pallas_segment_planes" in c.as_text()


def test_default_lambdarank_block_compiles(topo, ds_rank):
    kw, _ = compile_block(topo, ds_rank, {"objective": "lambdarank"})
    assert resolved(kw) == ("planes", "pallas", "pallas")


_BUNDLED = {}


def _bundled_block(topo, ds_onehot):
    """expo.train's block at 200K rows, compiled once for both tests."""
    if not _BUNDLED:
        kw, c = compile_block(topo, ds_onehot, {
            "objective": "binary", "min_data_in_leaf": 0,
            "min_sum_hessian_in_leaf": 100})
        _BUNDLED.update(kw=kw, c=c)
    return _BUNDLED["kw"], _BUNDLED["c"]


def test_default_bundled_block_compiles_at_32_planes(topo, ds_onehot):
    """The narrowest width the planes partition kernel meets: G = 10 device
    columns + 12 payload bytes pad to W = 32 (Higgs 64, MSLR 160), with the
    bundle maps as arguments and the view under its own phase."""
    binned = ds_onehot.construct()
    assert binned.has_bundles and len(binned.used_feature_indices) == 700
    kw, c = _bundled_block(topo, ds_onehot)
    assert resolved(kw) == ("planes", "pallas", "pallas")
    assert kw["bundle"] is not None and kw["num_bin_hist"] == 256
    _, width = partition.work_spec(binned.num_groups, False, kw["part_kernel"],
                                   kw["part_chunk"], kw["hist_chunk"],
                                   layout=kw["work_layout"])
    assert (binned.num_groups, width) == (10, 32)
    text = c.as_text()
    assert "lgbtpu/efb_view" in text and "partition_segment_planes_fused" in text


def test_bundled_view_places_runs_and_gathers_no_bin(topo, ds_onehot):
    """What PR 38 bought, read in the compiled text with no chip: until then
    226 of expo.train's 572 device ms an iteration gathered F x B = 168,000
    (g, h, count) triples out of the 30 KB bundled histogram at each of 509
    node searches (``f32[168000,2,3]``, 1,140 of them a slot) and copied the
    result channel-major through tiles padded 3 -> 128. In the ``runs`` form
    no instruction under ``lgbtpu/efb_view`` is a gather, none holds an
    array as long as the grid has cells along one axis, none over 64 KiB
    has 3 as its layout's minor dimension (the pair's (2, 3, 700) default
    bins, 17 KB, keep the layout XLA gave them in the parent too), the
    bundled features' runs leave their columns in one product, and
    ``route_table`` keeps the scope alive."""
    kw, c = _bundled_block(topo, ds_onehot)
    view = kw["bundle_view"]
    assert (view.form, view.alone, view.bundled, view.width) == \
        ("runs", 2, 698, 2)
    assert set(kw["bundle"]) >= {"sel", "sel_mine", "put", "map_fb"} \
        and not {"proj", "valid"} & set(kw["bundle"])
    cells = 700 * view.num_bin
    ops, bad = [], []
    for ln in c.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \S+ ([\w\-]+)\(", ln)
        if not m or "lgbtpu/efb_view" not in ln:
            continue
        ops.append((m.group(1), ln))
        if m.group(1) == "gather":
            bad.append(ln.strip()[:200])
        for dt, dims, lay in _arrays_of(ln):
            if max(dims) >= cells or (
                    dims[lay[0]] == 3
                    and _DT_BYTES[dt] * int(np.prod(dims)) > 1 << 16):
                bad.append(ln.strip()[:200])
    assert len(ops) > 20 and not bad, bad
    # root and pair: the three bfloat16 terms of (3, 10, 256) against sel
    assert sorted(ln.split(" = ")[1].split("{")[0] for op, ln in ops
                  if op == "convolution") == \
        ["f32[2,3,3,10,1400]", "f32[3,3,10,1400]"]
    # the routing table's translation, 254 times a tree, is still a view op
    assert [ln for _, ln in ops if "pred[256]" in ln]


def test_categorical_block_compiles_with_the_pallas_router(topo, ds_coded):
    """Until PR 35 ``has_categorical`` sent EVERY tree of such a job to the
    XLA ``fori_loop`` router (254 full-N rounds a tree, a categorical one an
    (N, 256) one-hot). Now the round's go-left table rides the SMEM table as
    eight bit-packed words and the job compiles the ``route_rows`` kernel:
    the block holds its custom call and no ``while`` under ``lgbtpu/route``.
    The categorical search is a phase of its own beside the numerical."""
    from lightgbm_tpu.obs import telemetry
    binned = ds_coded.construct()
    assert [m.num_bins for m in binned.bin_mappers] == \
        [13, 32, 8, 23, 255, 255, 240, 200]
    assert not binned.has_bundles
    telemetry.reset()
    kw, c = compile_block(topo, ds_coded, {
        "objective": "binary", "min_data_in_leaf": 0,
        "min_sum_hessian_in_leaf": 100})
    assert kw["hp"].has_categorical
    assert resolved(kw) == ("planes", "pallas", "pallas")
    assert telemetry.records("learner_path")[-1]["route_kernel"] == \
        "pallas_stream"
    text = c.as_text()
    assert "lgbtpu/route/route_rows" in text
    routed = [ln for ln in text.splitlines() if "lgbtpu/route" in ln]
    assert routed and not [ln for ln in routed if " while(" in ln]
    assert "lgbtpu/cat_scan" in text and "lgbtpu/split_scan" in text


# columns -> (layout, partition, histogram, router) of a dense table: both
# sides of the two width gates PR 33 took out (a packed row of 256 B, of
# 512 B) and of the router's VMEM budget, and epsilon.train's own width
WIDE = {300: ("planes", "pallas", "pallas", "pallas_stream"),
        600: ("planes", "pallas", "pallas", "pallas_wide"),
        2000: ("planes", "pallas", "pallas", "pallas_wide")}


def _compile_wide_block(topo, f):
    """A dense table of ``f`` columns under epsilon.train's parameters."""
    rng = np.random.RandomState(f)
    X = rng.randn(20_000, f).astype(np.float32)
    ds = lgb.Dataset(X, label=(X[:, :8].sum(axis=1) > 0).astype(np.float32))
    return compile_block(topo, ds, {
        "objective": "binary", "min_data_in_leaf": 1,
        "min_sum_hessian_in_leaf": 100})


@pytest.mark.parametrize("f", sorted(WIDE))
def test_wide_table_block_compiles(topo, f):
    """Until PR 32 the router held a block of EVERY column in VMEM twice and
    was refused from 504 columns ("Scoped allocation with size 16.05M and
    limit 16.00M exceeded scoped vmem limit"; 62.50M at 2,000), so no table
    wider than that trained on a TPU. Since PR 33 a wide table takes the
    planes layout and both planes kernels, as a narrow one does. The
    compile follows the width, not the rows, so 20,000 rows stand for
    epsilon.train's 400,000."""
    from lightgbm_tpu.obs import telemetry
    telemetry.reset()
    kw, c = _compile_wide_block(topo, f)
    rec = telemetry.records("learner_path")[-1]
    assert resolved(kw) + (rec["route_kernel"],) == WIDE[f]
    assert rec["packed_row_bytes"] == f + 12
    assert kw["num_bin_hist"] == 255
    assert rec["hist_pool_gb"] == pytest.approx(
        255 * f * 256 * 12 / 1e9)     # 255 bins a column, padded to 256
    text = c.as_text()
    assert "lgbtpu/route/route_rows" in text
    assert "partition_segment_planes_fused" in text
    assert "hist_pallas_segment_planes" in text


_DT_BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "u8": 1,
             "s8": 1, "pred": 1}


def _arrays_of(line):
    """(dtype, dims, minor_to_major) of every array in an HLO instruction's
    result type (a fusion's may be a tuple)."""
    m = re.match(r"^(\(.*?\)|\S+) [\w\-]+\(", line.split(" = ", 1)[1])
    return [(dt, [int(d) for d in dims.split(",")],
             [int(d) for d in lay.split(",")])
            for dt, dims, lay in re.findall(
                r"(\w+)\[([\d,]+)\]\{([\d,]+)", m.group(1) if m else "")
            if dt in _DT_BYTES]


def test_wide_block_keeps_bins_on_the_lanes_and_one_pool(topo):
    """What PR 36 bought, read in the compiled text with no chip: until then
    822 of epsilon.train's 1,340 device ms an iteration were six XLA ops a
    split (``copy``, ``reshape``) that carried the 6.1 MB histogram between
    the kernel, the pool and the scan through tiles padded 3 -> 128,
    because (F, B, 3) arrays keep the channels minor. In the F = 2,000
    block no instruction over 1 MB under ``lgbtpu/histogram`` or
    ``lgbtpu/tree_state`` may have a layout whose minor dimension is
    narrower than 128, and the split loop carries ONE pool that nothing
    copies (a slice of the pool fused into both children's writes made XLA
    copy all 1.57 GB of it twice a split). Keeps the next kernel or pool PR
    from bringing the conversions back."""
    f = 2000
    text = _compile_wide_block(topo, f)[1].as_text()
    pool = "f32[255,3,%d,256]" % f
    seen, narrow = 0, []
    for ln in text.splitlines():
        scope = re.search(r'op_name="[^"]*?lgbtpu/(\w+)', ln)
        if " = " not in ln or not scope \
                or scope.group(1) not in ("histogram", "tree_state"):
            continue
        for dt, dims, lay in _arrays_of(ln):
            if _DT_BYTES[dt] * int(np.prod(dims)) <= 1 << 20:
                continue
            seen += 1
            if dims[lay[0]] < 128:
                narrow.append(ln.strip()[:200])
    # the kernel's planes, the parent's row, both children's writes
    assert seen >= 4 and not narrow, narrow
    assert "f32[3,%d,256]" % f in text and "f32[%d,255,3]" % f not in text
    whiles = [ln for ln in text.splitlines()
              if re.search(r" while\(", ln) and pool in ln]
    assert whiles and all(
        ln.split(" while(")[0].count(pool) == 1 for ln in whiles)
    assert not [ln for ln in text.splitlines()
                if re.search(r"= %s\S* copy\(" % re.escape(pool), ln)]


def test_planes_kernels_compile_alone_at_2016_planes(topo):
    """The two planes kernels at epsilon.train's W = 2,016 and the chunks
    the width rules give there, on the cell's own (2, 2016, ~402K) buffer:
    a second or two each where the block takes minutes. And the histogram
    at the widest table the rule lets through."""
    from lightgbm_tpu.ops import histogram
    sh = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    i32 = sds((), jnp.int32)
    for f, n in ((2000, 400_000), (6896, 20_000)):
        chunk = histogram.planes_kernel_chunk(f)
        ch = partition.planes_part_chunk(f + 12)
        assert (chunk, ch) == {2000: (1024, 256), 6896: (128, 256)}[f]
        guard, w = partition.work_spec(f, False, "pallas", ch, chunk,
                                       layout="planes")
        work = sds((2, w, partition.planes_npad(n, guard, "pallas")),
                   jnp.uint8)
        jax.jit(lambda wk, p, s, c: histogram.hist_pallas_segment_planes(
            wk, p, s, c, num_bins=256, num_feat=f, exact=True,
            chunk=chunk)).lower(work, i32, i32, i32).compile()
        jax.jit(lambda wk, p, s, c, ft, tb:
                partition.partition_segment_planes_fused(
                    wk, p, s, c, ft, tb, ch=ch)).lower(
            work, i32, i32, i32, i32, sds((256,), jnp.bool_)).compile()
    assert histogram.planes_kernel_chunk(6897) == 0


def test_data_parallel_build_compiles_on_four_devices(topo, ds_binary,
                                                      cpu_mesh_devices):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.parallel.mesh import DataParallelTreeLearner
    cfg = Config.from_params(dict(BASE, objective="binary",
                                  tree_learner="data"))
    binned = ds_binary.construct()
    lrn = DataParallelTreeLearner(
        cfg, binned, Mesh(np.asarray(cpu_mesh_devices[:4]), ("data",)))
    # the mesh learners keep the XLA histogram (auto, PR 29)
    assert resolved(lrn.build_kwargs()) == ("planes", "pallas", "xla")
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    rows, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    n, f = lrn.padded_n, binned.num_features
    sds = jax.ShapeDtypeStruct
    low = jax.jit(lrn.sharded_build(mesh)).lower(
        sds((n, f), jnp.uint8, sharding=rows),
        sds((n, 3), jnp.float32, sharding=rows),
        _abstract(lrn.meta, rep), sds((f,), jnp.bool_, sharding=rep),
        _abstract(jax.random.PRNGKey(0), rep),
        sds((f,), jnp.bool_, sharding=rep))
    txt = low.as_text()     # what Comm asks for; XLA may rewrite it
    assert "all_reduce" in txt and "reduce_scatter" in txt
    low.compile()


# ------------------------------------ selectable paths the compiler accepts

@pytest.mark.parametrize("name,params,expect", [
    ("rows_fused_partition",            # the pre-round program
     {"tpu_work_layout": "rows"}, ("rows", "pallas", "xla")),
    ("resident",                        # auto's pick until PR 21 timed it
     {"tpu_resident_state": "on"}, ("resident", "pallas", "xla")),
    ("planes_pallas_hist", {"tpu_hist_kernel": "pallas"},
     ("planes", "pallas", "pallas")),
    ("planes_xla_hist",                 # auto's pick until PR 29 timed it
     {"tpu_hist_kernel": "xla"}, ("planes", "pallas", "xla")),
    ("rows_pallas_hist",
     {"tpu_work_layout": "rows", "tpu_hist_kernel": "pallas"},
     ("rows", "pallas", "pallas")),
    ("goss_compact",
     {"data_sample_strategy": "goss", "top_rate": 0.2, "other_rate": 0.1,
      "tpu_goss_compact": "on"}, ("planes", "pallas", "pallas")),
])
def test_selectable_path_compiles(topo, ds_binary, name, params, expect):
    kw, _ = compile_block(topo, ds_binary, dict(params, objective="binary"))
    assert resolved(kw) == expect, name
    if name == "goss_compact":
        assert kw["goss_compact_rows"] > 0


@pytest.fixture(scope="module")
def small_model():
    """Trained for real on the CPU: module scope, so it is built before the
    function-scoped ``as_on_tpu`` patch goes in."""
    X, y = make_higgs_like(4_000)
    return lgb.train({"objective": "binary", "num_leaves": 31,
                      "verbosity": -1}, lgb.Dataset(X, label=y),
                     num_boost_round=8), X


def test_serving_predict_compiles(topo, small_model):
    from lightgbm_tpu.serve import session
    bst, X = small_model
    pack, has_cat, has_linear = bst.inner._packed_model(0, 8)
    sh = SingleDeviceSharding(topo.devices[0])
    session._predict_bucket.lower(
        jax.ShapeDtypeStruct((65_536, X.shape[1]), jnp.float32, sharding=sh),
        _abstract(pack, sh), num_class=1, has_cat=has_cat,
        has_linear=has_linear).compile()
