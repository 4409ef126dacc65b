"""The cell crdb_kv.kv0 (PR 45; configuration crdb_kv, PR 41): the
manifest's entries looked up BY NAME, the mix's 64 templates of the one
UPSERT text and why no two share a key, the oracle's comparisons with the
acknowledged keys read back 64 a statement over sixteen connections, and
the cell's command end to
end on the CPU at 2,000 rows, 4 clients and 4 templates from a manifest of
its own, with the control (one acknowledged write withheld from the
reference) coming out as not correct."""

import json
import os
import types

from helpers import BENCH, HERE, ROOT, run_cell
from oracles import crdb_kv, crdb_kv0 as oracle

TINY = os.path.join(HERE, "manifest_kv0_tiny.json")
CELL, CONFIG, MIX = "crdb_kv.kv0", "crdb_kv", "kv0"
# metric -> the registry counters (num, den, per) or the span its file reads
METRICS = {
    "storage.wal_fsync_ms_per_stmt": "storage/wal.fsync",
    "storage.memtable_flushes_in_window": (["storage_flushes"], None, None),
    "storage.kv0_run_sorts_per_commit": (["storage_resolve_run_sorts"],
                                         ["storage_intent_commits"], None),
    "txn.kv0_server_retries_per_stmt": (["txn_retries"], None, "stmt"),
}
ALPHABET = ("ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz"
            "0123456789+/")


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def test_the_cells_entries_by_name_say_what_the_issue_asks():
    man = _json(ROOT, "BENCHMARK.json")
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200 and "64" in cell["why"]
    # no configuration of its own: the accepted one, its file unchanged
    assert [c["name"] for c in man["configs"]].count(CONFIG) == 1
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in METRICS:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "stmts_per_s", name
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert CELL not in e2e["latency_p95_ms"]["workloads"]
    for path in (os.path.join(ROOT, "BENCHMARK.json"), TINY):
        got = {m["name"]: m for m in _json(path)["per_layer"]}
        for name, reads in METRICS.items():
            spec = _json(BENCH, "metrics", name + ".json")
            for k in ("layer", "unit", "better", "source", "moves"):
                assert spec[k] == got[name][k], (path, name, k)
            if isinstance(reads, str):
                assert (spec["reader"], spec["args"]) == (
                    "span_totals", {"names": [reads], "per": "stmt"})
                continue
            assert spec["reader"] == "registry_counters"
            want = {"num": reads[0]}
            if reads[1]:
                want["den"] = reads[1]
            if reads[2]:
                want["per"] = reads[2]
            assert spec["args"] == want


def test_the_mix_is_kv0_with_keys_of_its_own_a_template():
    import traffic

    mix, kv95 = traffic.load_mix(MIX), traffic.load_mix("kv95")
    assert (mix["clients"], mix["oracle"], mix["param_sets"],
            mix["trace_seconds"]) == (64, "crdb_kv0", 512, 10.0)
    assert len(mix["templates"]) == 64
    write = kv95["templates"][1]
    for t in mix["templates"]:
        assert (t["name"], t["weight"], t["sql"]) == (
            "write", 1, write["sql"])
        assert t["params"] == write["params"]
    s = traffic.Stream(mix, 2**31 + 45, 3)
    keys = [p["k"] for sets in s.sets for p in sets]
    assert len(keys) == len(set(keys)) == 32_768  # the draws kv95 makes
    assert all(1_000_000 <= k < 1 << 62 for k in keys)
    assert all(p["v"] in ALPHABET for sets in s.sets for p in sets)
    # two clients meet on a key only at the same template AND position
    other = traffic.Stream(mix, 2**31 + 45, 9)
    assert other.sets == s.sets and (other.n, s.n) == (9, 3)
    used = [s.next()[0] for _ in range(4000)]
    assert len(set(used)) == 64
    tiny = traffic.load_mix("kv0_tiny")
    assert (tiny["clients"], tiny["oracle"], len(tiny["templates"])) == (
        4, "crdb_kv0", 4)
    assert {t["sql"] for t in tiny["templates"]} == {write["sql"]}


def _ctx(records, answers, control=False):
    mix = {"templates": [{"name": "write"}] * 4}
    asked = []

    class Conn:
        def query(self, sql):
            ks = [int(x) for x in
                  sql.split("IN (")[1].rstrip(")").split(", ")]
            asked.append(len(ks))
            rows = []
            for k in ks:
                a = answers(k)
                if isinstance(a, str):
                    return None, [], a
                rows.extend(a)
            return ["k", "v"], rows, None

        def close(self):
            pass

    ctx = types.SimpleNamespace(
        config={"alphabet": ALPHABET, "rows": 100, "sample_keys": 32},
        seed=5, mix=mix, records=records, control=control,
        connect=lambda: Conn())
    return ctx, asked


def _rec(t, k, err=None, v="q"):
    return {"t": t, "p": {"k": k, "v": v}, "err": err, "names": [],
            "rows": []}


def test_the_oracles_comparisons_and_its_batched_read_back():
    pre = lambda k: [[str(k), crdb_kv.preload_value(ALPHABET, 5, k)]]  # noqa: E731
    keys = list(range(1000, 1150))
    store = {k: [[str(k), "q"]] for k in keys}
    answers = lambda k: store.get(k, pre(k) if k < 100 else [])  # noqa: E731
    ctx, asked = _ctx([_rec(k % 4, k) for k in keys], answers, True)
    got = {c["name"]: c for c in oracle.check(ctx)}
    for name in ("acked_missing", "acked_different", "preloaded_changed",
                 "intent_blocked_reads", "statements_failed"):
        assert (got[name]["value"], got[name]["limit"]) == (0.0, 0.0), name
    assert got["acked_writes"]["value"] == 150
    assert (got["acked_writes"]["limit"], got["acked_writes"]["op"]) == (
        1.0, ">=")
    assert "reads_checked" not in got
    # 150 keys and the sample, 64 a statement, over several connections
    assert sorted(asked)[-2:] == [64, 64] and 22 in asked and len(asked) == 4
    control = got["control.acked_different_one_write_withheld"]
    assert control["control"] and control["value"] == 1.0
    # an idle window cannot pass
    idle, _ = _ctx([], answers)
    none = {c["name"]: c for c in oracle.check(idle)}
    assert none["acked_writes"]["value"] == 0 < none["acked_writes"]["limit"]
    # a lost write, another value, a changed preload
    lost, _ = _ctx([_rec(0, 5000)], answers)
    assert {c["name"]: c["value"] for c in oracle.check(lost)}[
        "acked_missing"] == 1
    other, _ = _ctx([_rec(0, 1000, v="r")], answers)
    assert {c["name"]: c["value"] for c in oracle.check(other)}[
        "acked_different"] == 1
    moved, _ = _ctx([_rec(0, 1000)], lambda k: [[str(k), "~"]] if k < 100
                    else answers(k))
    m = {c["name"]: c["value"] for c in oracle.check(moved)}
    assert m["preloaded_changed"] == m["preloaded_checked"] > 20
    # an intent left on ONE key of a batch: the batch is read key by key
    stuck, asked = _ctx(
        [_rec(0, k) for k in keys[:10]] + [_rec(1, 800, err="40001")],
        lambda k: "ERROR 40001" if k == 800 else answers(k))
    s = {c["name"]: c["value"] for c in oracle.check(stuck)}
    assert s["intent_blocked_reads"] == 1 and s["statements_failed"] == 1
    assert s["acked_missing"] == 0 and s["acked_writes"] == 10
    assert asked.count(11) == 1 and asked.count(1) == 11


def test_kv0_cell_rehearsal():
    """The cell's command on the CPU at 2,000 rows, 4 clients and 4
    templates (mix kv0_tiny): `correct`, nothing failed, every
    acknowledged write read back, no run sort, no compile for another key;
    the control fails."""
    rc, lines, err = run_cell("crdb_kv_tiny.kv0", seed=2**31 + 4545,
                              manifest=TINY, extra=["--control", "1"])
    assert rc == 0, err[-2000:]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 20
    m = last["metrics"]
    assert m["storage.kv0_run_sorts_per_commit"]["value"] == 0.0
    assert m["storage.memtable_flushes_in_window"]["value"] >= 0.0
    assert m["txn.kv0_server_retries_per_stmt"]["value"] < 0.5
    assert m["plancache.compiles_in_window"]["value"] == 0.0
    compares = {c["name"]: c for c in lines if c.get("step") == "compare"}
    for name in ("acked_missing", "acked_different", "preloaded_changed",
                 "intent_blocked_reads", "statements_failed"):
        assert compares[name]["value"] == 0.0 == compares[name]["limit"]
    assert compares["wal_fsync_armed"]["value"] == 1.0
    # distinct keys: 4 templates of 32 sets, walked by 4 clients
    assert 20 < compares["acked_writes"]["value"] <= 128
    control = compares["control.acked_different_one_write_withheld"]
    assert control["control_failed_as_it_must"] and control["value"] == 1.0
    warm = [ln for ln in lines if ln.get("step") == "warmup"]
    assert warm[-1]["compiles"] == 0 and len(warm) <= 4
