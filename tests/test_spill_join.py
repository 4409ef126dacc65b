"""Hybrid spill join + heavy-hitter skew routing (flow/external.py).

The Grace hash join's two escape hatches, each pinned against the
in-memory oracle bit-for-bit:

- hybrid degrade: partitions whose build side exceeds workmem reload as
  budget-sized sorted runs and merge-probe (ops.merge_join) instead of
  one oversized hash table — every join type, with the memory-monitor
  drain census (conftest autouse) proving the spill path releases all
  reservations;
- heavy-hitter routing: build-side reservoir sampling detects hot keys,
  pins their build rows resident, and streams their probe rows through a
  hot lane — plus the SPMD half: the shuffle plane's keep-local routing
  for hot hashes (parallel/shuffle.py).
"""

import jax
import numpy as np
import pytest

from cockroach_tpu import catalog as catalog_mod
from cockroach_tpu import coldata as cd
from cockroach_tpu.coldata.types import INT64, Schema
from cockroach_tpu.ops.hashing import hash_columns
from cockroach_tpu.parallel import dist, mesh as mesh_mod, shuffle as shuf
from cockroach_tpu.sql.rel import Rel
from cockroach_tpu.utils import metric, settings


def _catalog(seed, np_rows, nb_rows, nkeys, hot_key=None, hot_build=0,
             hot_probe=0):
    rng = np.random.default_rng(seed)
    pk = rng.integers(0, nkeys, np_rows).astype(np.int64)
    bk = rng.integers(0, int(nkeys * 1.25), nb_rows).astype(np.int64)
    if hot_key is not None:
        pk[:hot_probe] = hot_key
        bk[:hot_build] = hot_key
        rng.shuffle(pk)
        rng.shuffle(bk)
    cat = catalog_mod.Catalog()
    cat.add(catalog_mod.Table.from_strings(
        "p", Schema.of(k=INT64, w=INT64),
        {"k": pk, "w": rng.integers(0, 100, np_rows).astype(np.int64)}))
    cat.add(catalog_mod.Table.from_strings(
        "b", Schema.of(bk=INT64, v=INT64),
        {"bk": bk, "v": rng.integers(0, 100, nb_rows).astype(np.int64)}))
    return cat


def _run_join(cat, how, workmem, tile=2048, skew_frac=None):
    prev = {n: settings.get(n) for n in (
        "sql.distsql.workmem_bytes", "sql.distsql.tile_size",
        "sql.distsql.grace_skew_frac")}
    settings.set("sql.distsql.workmem_bytes", workmem)
    settings.set("sql.distsql.tile_size", tile)
    if skew_frac is not None:
        settings.set("sql.distsql.grace_skew_frac", skew_frac)
    try:
        r = (Rel.scan(cat, "p")
             .join(Rel.scan(cat, "b"), on=[("k", "bk")], how=how,
                   build_unique=False))
        return r.run()
    finally:
        for n, val in prev.items():
            settings.set(n, val)


def _canon(res):
    names = sorted(res.keys())
    recs = list(zip(*[np.asarray(res[n]).tolist() for n in names]))
    return sorted(recs, key=lambda t: tuple((x is None, x) for x in t))


@pytest.mark.parametrize("how", [
    "inner", pytest.param("left", marks=pytest.mark.slow), "semi",
    pytest.param("anti", marks=pytest.mark.slow)])
def test_hybrid_spill_merge_runs_match_oracle(how):
    """Forced spill with partitions past workmem: the build side reloads
    as sorted runs and merge-probes; output equals the in-memory join."""
    # a semi or anti join's build is pruned to its key (plan/prune.py):
    # half the bytes a row, so twice the rows for the same pressure
    nb = 60000 if how in ("semi", "anti") else 30000
    cat = _catalog(11, 8000, nb, nkeys=1500)
    oracle = _run_join(cat, how, workmem=2 << 30)
    spills0 = metric.GRACE_JOIN_SPILLS.value
    merge0 = metric.GRACE_JOIN_MERGE_PARTS.value
    got = _run_join(cat, how, workmem=1 << 16)
    assert metric.GRACE_JOIN_SPILLS.value > spills0, "join never spilled"
    assert metric.GRACE_JOIN_MERGE_PARTS.value > merge0, \
        "no partition degraded to merge runs (raise build size?)"
    assert _canon(got) == _canon(oracle)


@pytest.mark.parametrize("how", [
    "inner", pytest.param("left", marks=pytest.mark.slow), "semi",
    pytest.param("anti", marks=pytest.mark.slow)])
def test_skew_hot_lane_matches_oracle(how):
    """Heavy-hitter probe rows route through the resident hot build table;
    results stay identical and the routed-row metric moves."""
    cat = _catalog(13, 8000, 12000, nkeys=4000,
                   hot_key=77, hot_build=200, hot_probe=800)
    oracle = _run_join(cat, how, workmem=2 << 30, skew_frac=0.0)
    routed0 = metric.GRACE_JOIN_SKEW_ROUTED.value
    got = _run_join(cat, how, workmem=1 << 16, skew_frac=0.01)
    assert metric.GRACE_JOIN_SKEW_ROUTED.value > routed0, \
        "no probe rows took the hot lane"
    assert _canon(got) == _canon(oracle)


def test_skew_detection_skipped_when_hot_side_oversized():
    """When the hot keys' build rows would not fit the residency budget,
    the skew path stands down and the hybrid runs still bound memory."""
    cat = _catalog(17, 6000, 20000, nkeys=50,
                   hot_key=7, hot_build=12000, hot_probe=3000)
    oracle = _run_join(cat, "semi", workmem=2 << 30, skew_frac=0.0)
    routed0 = metric.GRACE_JOIN_SKEW_ROUTED.value
    got = _run_join(cat, "semi", workmem=1 << 16, skew_frac=0.05)
    assert metric.GRACE_JOIN_SKEW_ROUTED.value == routed0
    assert _canon(got) == _canon(oracle)


# -- SPMD half: hot hashes keep their rows local in the shuffle plane ------


@pytest.fixture(scope="module")
def mesh():
    return mesh_mod.make_mesh(8)


def _key_hash(schema, key_value):
    one = cd.from_host(
        schema, {"k": np.array([key_value], dtype=np.int64),
                 "v": np.array([0], dtype=np.int64)}, capacity=1)
    return np.asarray(
        hash_columns([one.cols[0]], [schema.types[0]], None))[:1]


def test_shuffle_hot_hashes_stay_local(mesh):
    """A 60%-skewed key overflows the plain hash router; with its hash in
    hot_hashes the rows never leave their device, the shuffle carries only
    the cold tail, and non-hot keys still coalesce one-device-each."""
    schema = cd.Schema.of(k=cd.INT64, v=cd.INT64)
    n, D, local = 4000, 8, 512
    rng = np.random.default_rng(3)
    k = np.where(rng.random(n) < 0.6, 0,
                 rng.integers(1, 50, n)).astype(np.int64)
    b = cd.from_host(schema, {"k": k, "v": np.arange(n, dtype=np.int64)},
                     capacity=local * D)
    sb = dist.shard_batch(b, mesh)
    hot_h = _key_hash(schema, 0)

    fn0 = shuf.make_shuffle(mesh, schema, (0,), local_capacity=local,
                            send_factor=1.0)
    _, ovf0 = fn0(sb)
    assert int(np.asarray(ovf0).sum()) > 0  # skew breaks the plain router

    fn1 = shuf.make_shuffle(mesh, schema, (0,), local_capacity=local,
                            send_factor=1.0, out_capacity=2 * local,
                            hot_hashes=hot_h)
    out, ovf1 = fn1(sb)
    assert int(np.asarray(ovf1).sum()) == 0

    rows, key_to_dev = 0, {}
    for d in range(D):
        shard_in = jax.tree_util.tree_map(
            lambda x: np.asarray(x)[d * local:(d + 1) * local], sb)
        hot_in = int(((shard_in.cols[0].data == 0) & shard_in.mask).sum())
        shard = jax.tree_util.tree_map(
            lambda x: np.asarray(x)[d * 2 * local:(d + 1) * 2 * local], out)
        ks = shard.cols[0].data[shard.mask]
        rows += int(shard.mask.sum())
        assert int((ks == 0).sum()) == hot_in, "hot rows moved devices"
        for key in np.unique(ks[ks != 0]):
            assert key_to_dev.setdefault(key, d) == d, "non-hot key split"
    assert rows == n


def test_shuffle_hot_routing_with_replicated_build_is_exact(mesh):
    """The routing contract end to end: non-hot build rows live only on
    their hash-owner device, hot build rows are replicated everywhere;
    joining each post-shuffle shard against its device's build slice
    reproduces the full join exactly."""
    schema = cd.Schema.of(k=cd.INT64, v=cd.INT64)
    n, D, local = 3000, 8, 512
    rng = np.random.default_rng(5)
    k = np.where(rng.random(n) < 0.5, 7,
                 rng.integers(8, 60, n)).astype(np.int64)
    v = np.arange(n, dtype=np.int64)
    sb = dist.shard_batch(
        cd.from_host(schema, {"k": k, "v": v}, capacity=local * D), mesh)
    bk = np.arange(0, 60, dtype=np.int64)
    hot_h = _key_hash(schema, 7)

    fn = shuf.make_shuffle(mesh, schema, (0,), local_capacity=local,
                           send_factor=2.0, out_capacity=2 * local,
                           hot_hashes=hot_h)
    out, ovf = fn(sb)
    assert int(np.asarray(ovf).sum()) == 0

    # per-device build slice: owned non-hot keys + replicated hot key
    bh = np.concatenate([_key_hash(schema, int(key)) for key in bk])
    owner = (bh % np.uint64(D)).astype(np.int64)
    got = []
    for d in range(D):
        dev_keys = set(bk[(owner == d) & (bk != 7)].tolist()) | {7}
        bmap = {int(key): int(key) * 100 for key in dev_keys}
        shard = jax.tree_util.tree_map(
            lambda x: np.asarray(x)[d * 2 * local:(d + 1) * 2 * local], out)
        m = shard.mask
        for key, val in zip(shard.cols[0].data[m], shard.cols[1].data[m]):
            assert int(key) in bmap, "row on a device missing its build rows"
            got.append((int(val), bmap[int(key)]))
    want = sorted((int(vv), int(kk) * 100) for vv, kk in zip(v, k))
    assert sorted(got) == want
