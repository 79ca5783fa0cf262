"""Strategy-file wire-format tests.

Round-trips through our hand-rolled proto2 codec and — when protoc is
available — cross-validates against the *reference's own* strategy.proto
schema via ``protoc --decode/--encode``, proving byte-level compatibility
without a protobuf runtime dependency.  Also covers the provenance
sidecar: round-trip, hash staleness, corrupt-sidecar tolerance, and the
``strategy_provenance`` event a traced load emits.
"""

import json
import os
import shutil
import subprocess

import pytest

from flexflow_tpu.config import DeviceType, ParallelConfig
from flexflow_tpu.observability import events
from flexflow_tpu.parallel.strategy import (load_strategies_from_file,
                                            read_provenance,
                                            save_strategies_to_file,
                                            sidecar_path,
                                            write_provenance)

REF_PROTO = "/root/reference/src/runtime/strategy.proto"


def sample_strategies():
    return {
        "conv1": ParallelConfig(DeviceType.TPU, (4, 1, 2, 1), tuple(range(8))),
        "dense_1": ParallelConfig(DeviceType.TPU, (2, 4), tuple(range(8))),
        "embed_cpu": ParallelConfig(DeviceType.CPU, (1, 1), (0,)),
    }


def test_round_trip(tmp_path):
    path = str(tmp_path / "strategy.pb")
    strategies = sample_strategies()
    save_strategies_to_file(path, strategies)
    loaded = load_strategies_from_file(path)
    assert set(loaded) == set(strategies)
    for k in strategies:
        assert loaded[k].dims == strategies[k].dims
        assert loaded[k].device_ids == strategies[k].device_ids
        assert loaded[k].device_type == strategies[k].device_type


def test_reference_order_import(tmp_path):
    path = str(tmp_path / "s.pb")
    save_strategies_to_file(path, {"op": ParallelConfig(DeviceType.TPU, (1, 2, 1, 4), (0,) * 8)})
    loaded = load_strategies_from_file(path, reference_order=True)
    assert loaded["op"].dims == (4, 1, 2, 1)


# ---------------------------------------------------------------------------
# provenance sidecar
# ---------------------------------------------------------------------------

def test_provenance_round_trip(tmp_path):
    path = str(tmp_path / "s.pb")
    meta = {"engine": "mcmc", "budget": 500, "seed": 7, "num_devices": 8,
            "best_ms": 3.21,
            "ops": {"conv1": {"dims": "4x1x2x1", "fwd_ms": 0.1}}}
    save_strategies_to_file(path, sample_strategies(), provenance=meta)
    got = read_provenance(path)
    assert got is not None
    for k, v in meta.items():
        assert got[k] == v
    # the stamper's own fields
    assert got["provenance_version"] == 1
    assert got["strategy_file"] == "s.pb"
    assert got["content_hash"].startswith("sha256:")
    assert got["created_unix"] > 0


def test_provenance_absent_without_metadata(tmp_path):
    path = str(tmp_path / "s.pb")
    save_strategies_to_file(path, sample_strategies())
    import os
    assert not os.path.exists(sidecar_path(path))
    assert read_provenance(path) is None


def test_corrupt_sidecar_warns_and_is_ignored(tmp_path):
    path = str(tmp_path / "s.pb")
    save_strategies_to_file(path, sample_strategies())
    for payload in ('{"truncat', '[1, 2, 3]', ""):
        with open(sidecar_path(path), "w") as f:
            f.write(payload)
        with pytest.warns(UserWarning, match="corrupt strategy sidecar"):
            assert read_provenance(path) is None
        # and a load never breaks on it
        assert set(load_strategies_from_file(path)) == \
            set(sample_strategies())


def test_traced_load_emits_provenance_event(tmp_path, monkeypatch):
    trace = tmp_path / "t.jsonl"
    monkeypatch.setenv("FF_TELEMETRY", "1")
    monkeypatch.setenv("FF_TELEMETRY_FILE", str(trace))
    events.reset_active()
    try:
        path = str(tmp_path / "s.pb")
        save_strategies_to_file(
            path, sample_strategies(),
            provenance={"engine": "native", "budget": 9, "seed": 1,
                        "best_ms": 5.5})
        load_strategies_from_file(path)  # sidecar ok
        # overwrite the .pb without re-stamping -> hash mismatch
        save_strategies_to_file(
            path, {"op": ParallelConfig(DeviceType.TPU, (1, 1), (0,))})
        load_strategies_from_file(path)  # sidecar stale
        nosc = str(tmp_path / "bare.pb")
        save_strategies_to_file(nosc, sample_strategies())
        load_strategies_from_file(nosc)  # sidecar missing
    finally:
        events.reset_active()
    with open(trace) as f:
        recs = [json.loads(line) for line in f if line.strip()]
    prov = [r["attrs"] for r in recs
            if r.get("name") == "strategy_provenance"]
    assert [p["provenance"] for p in prov] == ["ok", "stale", "missing"]
    assert prov[0]["engine"] == "native" and prov[0]["budget"] == 9
    assert prov[0]["best_ms"] == 5.5 and prov[0]["num_ops"] == 3


def test_untraced_load_makes_zero_event_log_calls(tmp_path, monkeypatch):
    monkeypatch.delenv("FF_TELEMETRY", raising=False)
    events.reset_active()
    monkeypatch.setattr(
        events.EventLog, "_write",
        lambda self, rec: (_ for _ in ()).throw(
            AssertionError(f"event-log call while disabled: {rec}")))
    path = str(tmp_path / "s.pb")
    save_strategies_to_file(path, sample_strategies(),
                            provenance={"engine": "mcmc"})
    assert set(load_strategies_from_file(path)) == set(sample_strategies())


def test_write_provenance_rebinds_hash(tmp_path):
    path = str(tmp_path / "s.pb")
    save_strategies_to_file(path, sample_strategies())
    write_provenance(path, {"engine": "mcmc"})
    h1 = read_provenance(path)["content_hash"]
    save_strategies_to_file(
        path, {"op": ParallelConfig(DeviceType.TPU, (2, 1), (0, 1))},
        provenance={"engine": "mcmc"})
    h2 = read_provenance(path)["content_hash"]
    assert h1 != h2  # the sidecar follows the bytes it describes


@pytest.mark.skipif(shutil.which("protoc") is None
                    or not os.path.exists(REF_PROTO),
                    reason="needs protoc and the reference checkout's "
                           "strategy.proto, which is outside this repo")
def test_wire_compatible_with_reference_proto(tmp_path):
    path = str(tmp_path / "strategy.pb")
    save_strategies_to_file(path, sample_strategies())
    # Decode our bytes with the reference schema.
    with open(path, "rb") as f:
        out = subprocess.run(
            ["protoc", f"--proto_path=/root/reference/src/runtime",
             "--decode=FFProtoBuf.Strategy", "strategy.proto"],
            stdin=f, capture_output=True, check=True)
    text = out.stdout.decode()
    assert 'name: "conv1"' in text
    assert "dims: 4" in text and "device_type: CPU" in text

    # Re-encode the decoded text with protoc and parse with our codec.
    enc = subprocess.run(
        ["protoc", f"--proto_path=/root/reference/src/runtime",
         "--encode=FFProtoBuf.Strategy", "strategy.proto"],
        input=out.stdout, capture_output=True, check=True)
    path2 = str(tmp_path / "re.pb")
    with open(path2, "wb") as f:
        f.write(enc.stdout)
    loaded = load_strategies_from_file(path2)
    orig = sample_strategies()
    assert {k: (v.dims, v.device_ids) for k, v in loaded.items()} == \
           {k: (v.dims, v.device_ids) for k, v in orig.items()}
