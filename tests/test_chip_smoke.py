"""Rehearsal of chip_smoke.py's phases at tiny size on the CPU mesh.

The script itself refuses to run without a TPU; its phases are plain
functions, so the control flow, the oracles and the four-device sharding
rules are checked here, where a fault costs no chip time."""

import json

import jax

import chip_smoke


def _lines(capsys) -> list[dict]:
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]


def test_main_refuses_to_run_without_a_tpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--chips", "4"]) != 0
    cap = capsys.readouterr()
    assert "no TPU" in cap.err and "no CPU mode" in cap.err
    assert '"ok"' not in cap.out and cap.out.strip() == ""


def test_kv_phase_tiny(capsys):
    out = chip_smoke.phase_kv(n_keys=4096, ops=32, concurrency=8, n_rows=60)
    assert out["ycsb"]["bit_identical"] and out["rows"] == 60
    # on the CPU `auto` never selects a Pallas kernel
    assert out["scan_filter"] == "jnp" and out["merge"] in ("jnp", "none")
    writes = [ln for ln in _lines(capsys) if ln.get("step") == "sql_writes"]
    assert writes and writes[0]["lost"] == 0
    assert writes[0]["single_row_txns"] == 30
    assert writes[0]["multi_row_txns"] == 3


def test_shuffle_phase_tiny(capsys):
    assert len(jax.devices()) >= 4
    out = chip_smoke.phase_shuffle(sf=0.005, n_devices=4)
    assert len(out["lineitem_rows_per_device"]) == 4
    assert out["all_to_all"] >= 1
    lines = _lines(capsys)
    assert any(ln.get("equals_pandas") for ln in lines)
    assert any(ln.get("distributed_equals_single") for ln in lines)


def test_device_phase_reports_the_cache_in_force(capsys, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    out = chip_smoke.phase_device()
    assert out["platform"] == "cpu" and out["count"] == len(jax.devices())
    assert out["compile_cache_dir"].endswith(".jax_cache")
    assert out["compile_cache_from_env"] is False
    assert out["float_bitcast_ok"] is True
