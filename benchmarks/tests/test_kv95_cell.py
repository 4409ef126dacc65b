"""The cell crdb_kv.kv95 and its configuration crdb_kv (PR 41): the
manifest's entries looked up BY NAME (later PRs append), the mix's weights
and ranges, the oracle's reference against the loader's preload, the
loader's refusal of a program without UPSERT, and the cell's command end to
end on the CPU at 2,000 rows and 4 clients from a manifest of its own, with
the control (one acknowledged write withheld from the reference) coming out
as not correct."""

import json
import os
import types

import numpy as np
import pytest

from helpers import BENCH, HERE, ROOT, run_cell
from loaders import crdb_kv as loader
from oracles import crdb_kv as oracle

KV95_TINY = os.path.join(HERE, "manifest_kv95_tiny.json")
CELL, CONFIG, MIX = "crdb_kv.kv95", "crdb_kv", "kv95"
# metric -> the registry counters (num, den) or the span its file reads
METRICS = {
    "storage.point_reads_per_stmt": (["sql_kv_point_reads"], None),
    "storage.table_decodes_per_stmt": (["sql_kv_table_decodes"], None),
    "storage.run_sorts_per_commit": (["storage_resolve_run_sorts"],
                                     ["storage_intent_commits"]),
    "storage.block_cache_hit_share": (
        ["storage_blockcache_hits"],
        ["storage_blockcache_hits", "storage_blockcache_misses"]),
    "txn.server_retries_per_stmt": (["txn_retries"], None),
    "storage.engine_lock_wait_ms_per_stmt": "storage/engine.lock_wait",
    "plancache.entry_wait_ms_per_stmt": "sql.plancache.entry_wait",
}
ALPHABET = ("ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz"
            "0123456789+/")


def _manifest():
    return json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def test_the_cells_entries_by_name_say_what_the_issue_asks():
    man = _manifest()
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1)
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    cfg = json.load(open(os.path.join(ROOT, entry["file"])))
    assert entry["file"] == "benchmarks/configs/crdb_kv.json"
    assert cfg["source"] == entry["source"]
    for word in ("pkg/workload/kv", "kv95", "--read-percent=95",
                 "--concurrency=64", "1 node"):
        assert word in entry["source"], word
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert len(cell["why"]) <= 200 and "64" in cell["why"]
    assert entry["reduced"] == cfg["reduced"] == ["nodes", "rows"]
    assert set(cfg["reduced_why"]) == {"nodes", "rows"}
    assert cfg["rows"] == 1_000_000 and cfg["nodes"] == 1
    assert cfg["platform"] == "tpu" and cfg["loader"] == "crdb_kv"
    # the node's own widths and the source's synced WAL
    assert cfg["engine"] == {"key_width": 64, "val_width": 128,
                             "wal_fsync": True}
    assert set(cfg["guarantees"]) >= {
        "isolation", "durability", "read_your_acknowledged_writes",
        "no_failed_statement"}
    assert "wal_fsync=True" in cfg["guarantees"]["durability"]
    assert "serializable" in cfg["guarantees"]["isolation"]
    assert cfg["alphabet"] == ALPHABET and cfg["sample_keys"] == 4096
    assert len(cfg["assumed"]) >= 5 and "PLACEHOLDER" not in json.dumps(cfg)
    for name in ("loaders", "oracles"):
        assert os.path.exists(os.path.join(BENCH, name, "crdb_kv.py"))
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in METRICS:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "stmts_per_s", name
    assert by_name["storage.table_decodes_per_stmt"]["better"] == "lower"
    assert by_name["storage.run_sorts_per_commit"]["better"] == "lower"
    assert by_name["storage.block_cache_hit_share"]["unit"] == "%"
    # one configuration, one cell: kv0 waits until this one stands
    assert [w["name"] for w in man["workloads"]
            if w["config"] == CONFIG] == [CELL]


def test_the_mix_is_kv95():
    import traffic

    mix = traffic.load_mix(MIX)
    assert (mix["clients"], mix["oracle"], mix["param_sets"],
            mix["trace_seconds"]) == (64, "crdb_kv", 16384, 10.0)
    read, write = mix["templates"]
    assert (read["name"], read["weight"]) == ("read", 95)
    assert (write["name"], write["weight"]) == ("write", 5)
    assert read["sql"] == "SELECT k, v FROM kv WHERE k IN ({k})"
    assert write["sql"] == "UPSERT INTO kv (k, v) VALUES ({k}, '{v}')"
    assert read["params"] == {"k": {"gen": "uniform_int", "lo": 0,
                                    "hi": 999_999}}
    assert write["params"]["k"] == {"gen": "uniform_int", "lo": 1_000_000,
                                    "hi": (1 << 62) - 1}
    assert write["params"]["v"] == {"gen": "choice",
                                    "values": list(ALPHABET)}
    cfg = json.load(open(os.path.join(BENCH, "configs", "crdb_kv.json")))
    # the loader's probe is the mix's write statement, rendered
    assert cfg["probe_statement"] == write["sql"].format(k=1_000_000, v="A")
    s = traffic.Stream(mix, 2**31 + 41, 3)
    kinds = [s.next()[0] for _ in range(4000)]
    assert 0.93 < kinds.count(0) / len(kinds) < 0.97
    assert len({json.dumps(p) for p in s.sets[0]}) > 16_000  # distinct keys
    for p in s.sets[1][:256]:
        assert 1_000_000 <= p["k"] < 1 << 62 and p["v"] in ALPHABET
    # every client walks the same sets from its own offset
    other = traffic.Stream(mix, 2**31 + 41, 9)
    assert other.sets == s.sets and (other.n, s.n - 4000) == (9, 3)
    warm = [sql for _j, _p, sql in s.warmup()]
    assert len(warm) == 4 and "k IN (0)" in warm[0]
    assert "k IN (999999)" in warm[1]
    assert f"({(1 << 62) - 1}, " in warm[3]


@pytest.mark.parametrize("seed", [0, 11, 2**31 + 4141, 2**32 + 5])
def test_the_oracles_preload_is_the_loaders(seed):
    keys = np.array([0, 1, 2, 63, 64, 999_999, 123_456], dtype=np.int64)
    idx = loader.value_index(seed, keys)
    assert idx.min() >= 0 and idx.max() < 64
    for k, i in zip(keys, idx):
        assert oracle.preload_value(ALPHABET, seed, int(k)) == ALPHABET[i]
    # all 64 values appear, so no UPSERT of the mix mints a dictionary code
    assert len(set(loader.value_index(seed, np.arange(4096)))) == 64
    ref = oracle.Reference(ALPHABET, seed, 1000)
    assert ref.read(1000) == [] and ref.read(-1) == []
    assert ref.read(7) == [["7", oracle.preload_value(ALPHABET, seed, 7)]]
    ref.upsert(7, "z")
    ref.upsert(1 << 61, "y")
    assert ref.read(7) == [["7", "z"]]
    assert ref.read(1 << 61) == [[str(1 << 61), "y"]]


def _ctx(records, answers, control=False):
    """A ctx whose fresh connection answers from `answers` (key -> rows or
    an error string)."""
    mix = {"templates": [{"name": "read"}, {"name": "write"}]}

    class Conn:
        def query(self, sql):
            ks = [int(x) for x in
                  sql.split("IN (")[1].rstrip(")").split(", ")]
            rows = []
            for k in ks:
                a = answers(k)
                if isinstance(a, str):
                    return None, [], a
                rows.extend(a)
            return ["k", "v"], rows, None

        def close(self):
            pass

    return types.SimpleNamespace(
        config={"alphabet": ALPHABET, "rows": 100, "sample_keys": 32},
        seed=5, mix=mix, records=records, control=control,
        connect=lambda: Conn())


def _rec(t, k, rows=None, err=None, v="q"):
    p = {"k": k} if t == 0 else {"k": k, "v": v}
    return {"t": t, "p": p, "err": err, "names": ["k", "v"] if t == 0
            else [], "rows": rows or []}


def test_the_oracles_five_comparisons():
    pre = lambda k: [[str(k), oracle.preload_value(ALPHABET, 5, k)]]  # noqa: E731
    store = {500: [["500", "q"]], 600: [["600", "q"]]}
    answers = lambda k: store.get(k, pre(k) if k < 100 else [])  # noqa: E731
    good = [_rec(0, 3, pre(3)), _rec(1, 500), _rec(1, 600),
            _rec(0, 500, [["500", "q"]]), _rec(0, 500, [])]
    got = {c["name"]: c for c in oracle.check(_ctx(good, answers, True))}
    for name in ("reads_wrong", "acked_missing", "acked_different",
                 "preloaded_changed", "intent_blocked_reads",
                 "statements_failed"):
        assert (got[name]["value"], got[name]["limit"]) == (0.0, 0.0), name
    assert got["reads_checked"]["value"] == 3
    assert got["acked_writes"]["value"] == 2
    assert got["preloaded_checked"]["value"] > 20
    control = got["control.acked_different_one_write_withheld"]
    assert control["control"] and control["value"] == 1.0
    # (a) a wrong value, a missing row, an extra row
    for rows in ([["3", "~"]], [], pre(3) + pre(4)):
        bad = oracle.check(_ctx([_rec(0, 3, rows)], answers))
        assert {c["name"]: c["value"] for c in bad}["reads_wrong"] == 1
    # (b) an acknowledged write that is gone, or has another value
    lost = {c["name"]: c["value"] for c in oracle.check(
        _ctx([_rec(1, 700)], answers))}
    assert lost["acked_missing"] == 1 and lost["acked_different"] == 0
    other = {c["name"]: c["value"] for c in oracle.check(
        _ctx([_rec(1, 500, v="r")], answers))}
    assert other["acked_different"] == 1
    # (c) a preloaded row that changed
    moved = {c["name"]: c["value"] for c in oracle.check(_ctx(
        good, lambda k: [[str(k), "~"]] if k < 100 else answers(k)))}
    assert moved["preloaded_changed"] == moved["preloaded_checked"]
    # (d) an intent left on a key whose write FAILED, and (e) the failure
    stuck = {c["name"]: c["value"] for c in oracle.check(_ctx(
        [_rec(1, 800, err="40001")],
        lambda k: "ERROR 40001" if k == 800 else answers(k)))}
    assert stuck["intent_blocked_reads"] == 1
    assert stuck["statements_failed"] == 1 and stuck["acked_writes"] == 0


def test_the_loader_refuses_a_program_without_upsert(monkeypatch):
    from cockroach_tpu.sql import parser

    cfg = json.load(open(os.path.join(BENCH, "configs", "crdb_kv.json")))
    loader.refuse_without_upsert(cfg)  # this program has it

    def parents(text):  # the parent's grammar: UPSERT is no statement
        if text.lstrip().lower().startswith("upsert"):
            raise SyntaxError("expected 'select', got 'upsert' at 0")
        return real(text)

    real = parser.parse_statement
    monkeypatch.setattr(parser, "parse_statement", parents)
    with pytest.raises(SystemExit) as e:
        loader.load(cfg, 1, "/nonexistent")  # before any node or data
    assert "parser" in str(e.value) and "UPSERT" in str(e.value)
    monkeypatch.setattr(parser, "parse_statement",
                        lambda text: real("INSERT" + text.lstrip()[6:]))
    with pytest.raises(SystemExit) as e:
        loader.refuse_without_upsert(cfg)
    assert "did not parse as an UPSERT" in str(e.value)


def test_metric_files_agree_with_their_manifest_entries_by_name():
    for path in (os.path.join(ROOT, "BENCHMARK.json"), KV95_TINY):
        got = {m["name"]: m for m in json.load(open(path))["per_layer"]}
        for name, reads in METRICS.items():
            spec = json.load(open(os.path.join(BENCH, "metrics",
                                               name + ".json")))
            for k in ("layer", "unit", "better", "source", "moves"):
                assert spec[k] == got[name][k], (path, name, k)
            if isinstance(reads, str):
                assert spec["reader"] == "span_totals"
                assert spec["args"] == {"names": [reads], "per": "stmt"}
                continue
            assert spec["reader"] == "registry_counters"
            assert spec["args"]["num"] == reads[0]
            assert spec["args"].get("den") == reads[1]
            assert (spec["args"].get("per") == "stmt") == (reads[1] is None)


def test_registry_counters_reader():
    from cockroach_tpu.utils import metric
    from readers import registry_counters as rc

    a = metric.DEFAULT.counter("test_kv95_a")
    b = metric.DEFAULT.counter("test_kv95_b")
    ctx = types.SimpleNamespace(statements=10)
    st = rc.begin(ctx, ["test_kv95_a"], per="stmt")
    a.inc(5)
    assert rc.read(ctx, st, ["test_kv95_a"], per="stmt") == 0.5
    st = rc.begin(ctx, ["test_kv95_a"], den=["test_kv95_a", "test_kv95_b"],
                  scale=100.0)
    assert rc.read(ctx, st, ["test_kv95_a"],
                   den=["test_kv95_a", "test_kv95_b"], scale=100.0) is None
    a.inc(1)
    b.inc(3)
    assert rc.read(ctx, st, ["test_kv95_a"],
                   den=["test_kv95_a", "test_kv95_b"], scale=100.0) == 25.0
    # a program without the counter: no reading, no error
    assert rc.begin(ctx, ["no_such_counter"], per="stmt") == (None, None)
    assert rc.read(ctx, (None, None), ["no_such_counter"],
                   per="stmt") is None


def test_kv95_cell_rehearsal():
    """The cell's command on the CPU at 2,000 rows and 4 clients (mix
    kv95_tiny: kv95's templates and weights over the smaller key ranges):
    `correct`, nothing failed, the point route serves every read, no table
    decode, no run sort, no compile for another key; the control fails."""
    rc, lines, err = run_cell("crdb_kv_tiny.kv95", seed=2**31 + 4141,
                              manifest=KV95_TINY, extra=["--control", "1"])
    assert rc == 0, err[-2000:]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 50
    m = last["metrics"]
    assert 0.85 <= m["storage.point_reads_per_stmt"]["value"] <= 1.0
    assert m["storage.table_decodes_per_stmt"]["value"] == 0.0
    assert m["storage.run_sorts_per_commit"]["value"] == 0.0
    assert m["plancache.compiles_in_window"]["value"] == 0.0
    assert m["txn.server_retries_per_stmt"]["value"] < 0.5
    compares = {c["name"]: c for c in lines if c.get("step") == "compare"}
    for name in ("reads_wrong", "acked_missing", "acked_different",
                 "preloaded_changed", "intent_blocked_reads",
                 "statements_failed"):
        assert compares[name]["value"] == 0.0 == compares[name]["limit"]
    assert compares["wal_fsync_armed"]["value"] == 1.0
    assert compares["acked_writes"]["value"] >= 1  # the control needs one
    assert compares["reads_checked"]["value"] > 40
    control = compares["control.acked_different_one_write_withheld"]
    assert control["control_failed_as_it_must"] and control["value"] == 1.0
    load = next(ln for ln in lines if ln.get("step") == "load")
    assert load["n_rows"] == 2000 and load["run_capacities"] == [2048]
    warm = [ln for ln in lines if ln.get("step") == "warmup"]
    assert warm[-1]["compiles"] == 0 and len(warm) <= 4


def test_the_tiny_mix_is_kv95_over_smaller_ranges():
    import traffic

    real, tiny = traffic.load_mix("kv95"), traffic.load_mix("kv95_tiny")
    assert [(t["name"], t["weight"], t["sql"]) for t in tiny["templates"]
            ] == [(t["name"], t["weight"], t["sql"])
                  for t in real["templates"]]
    assert tiny["clients"] == 4 and tiny["oracle"] == "crdb_kv"
    assert tiny["templates"][0]["params"]["k"]["hi"] == 1999
    assert tiny["templates"][1]["params"]["k"]["lo"] == 2000
