"""Deployment profiles (ISSUE 20): schema round-trip + validator,
per-knob precedence (explicit env/flag > profile > default), prior-seeded
first-batch routing vs cold EWMAs, fingerprint-mismatch warning, the
consistent knob-parse diagnostic, and the autotune/replay derivations.

Daemon warm-start snapshot coverage (save on close / reload on restart)
lives in test_serve_daemon.py beside the other lifecycle tests.
"""

import json
import logging
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fgumi_tpu.ops.router import OffloadRouter  # noqa: E402
from fgumi_tpu.tune import profile as profmod  # noqa: E402
from fgumi_tpu.tune.profile import (KNOB_ENV, ProfileError,  # noqa: E402
                                    fingerprint_host, load_profile,
                                    validate_profile, write_profile)

KNOB_VARS = tuple(KNOB_ENV.values())


@pytest.fixture(autouse=True)
def _isolated_profile_state(monkeypatch):
    """Each test starts with no applied profile, no knob env vars, and a
    cold router; apply_profile's own env writes are swept after."""
    for var in KNOB_VARS + ("FGUMI_TPU_PROFILE",):
        monkeypatch.delenv(var, raising=False)
    profmod.reset_applied_for_tests()
    from fgumi_tpu.ops import router as router_mod

    router_mod.ROUTER.reset()
    saved = {v: os.environ.get(v) for v in KNOB_VARS}
    yield
    for var, old in saved.items():
        if old is None:
            os.environ.pop(var, None)
        else:
            os.environ[var] = old
    profmod.reset_applied_for_tests()
    router_mod.ROUTER.reset()


def _profile(**over):
    base = {
        "schema_version": 1,
        "tool": "fgumi-tpu tune",
        "created_unix": 1700000000,
        "source": "autotune",
        "fingerprint": fingerprint_host(),
        "knobs": {"feeder_depth": 3, "coalesce_window_ms": 5.0},
        "priors": {
            "router": {"link_mbps": 120.0, "overhead_s": 0.01,
                       "dispatch_wall_s": 0.02,
                       "host_mcells_per_s": 50.0,
                       "filter_keep_rate": 0.7},
        },
    }
    base.update(over)
    return base


# ------------------------------------------------------- schema round-trip


def test_profile_round_trip(tmp_path):
    path = str(tmp_path / "prof.json")
    write_profile(path, _profile())
    loaded = load_profile(path)
    assert loaded == _profile()
    # atomic commit: no temp residue
    assert os.listdir(tmp_path) == ["prof.json"]


@pytest.mark.parametrize("mutate, needle", [
    (lambda p: p.pop("schema_version"), "schema_version"),
    (lambda p: p.update(schema_version=99), "newer"),
    (lambda p: p.pop("fingerprint"), "fingerprint"),
    (lambda p: p.update(source="guesswork"), "source"),
    (lambda p: p["knobs"].update(bogus_knob=1), "unknown knob"),
    (lambda p: p["knobs"].update(feeder_depth=1), "floor"),
    (lambda p: p["knobs"].update(feeder_depth="two"), "wrong type"),
    (lambda p: p["knobs"].update(coalesce_window_ms=-1), "floor"),
    (lambda p: p["knobs"].update(shape_buckets="9.9"), "SHAPE_BUCKETS"),
    (lambda p: p["knobs"].update(mesh="dp0"), "FGUMI_TPU_MESH"),
    (lambda p: p["priors"].update(router={"link_mbps": -5}), "link_mbps"),
    (lambda p: p["priors"].update(
        router={"filter_keep_rate": 1.5}), "ceiling"),
    (lambda p: p["priors"].update(crossover={}), "priors.crossover"),
    (lambda p: p["priors"].update(
        router={"mesh": {"0": {}}}), "device count"),
])
def test_validator_names_token_and_grammar(mutate, needle):
    prof = _profile()
    mutate(prof)
    with pytest.raises(ProfileError) as ei:
        validate_profile(prof)
    msg = str(ei.value)
    assert needle in msg
    # the one consistent diagnostic: offending token, then the grammar
    assert "expected" in msg


def test_load_profile_errors_are_exit2_diagnostics(tmp_path):
    missing = str(tmp_path / "nope.json")
    with pytest.raises(ProfileError, match="unreadable"):
        load_profile(missing)
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    with pytest.raises(ProfileError, match="not valid JSON"):
        load_profile(str(bad))


def test_knob_parse_errors_share_one_grammar():
    """Satellite: FGUMI_TPU_SHAPE_BUCKETS, the mesh spec, and profile
    fields all name the offending token and the accepted grammar."""
    from fgumi_tpu.ops.datapath import parse_shape_buckets
    from fgumi_tpu.parallel.mesh import MeshConfigError, parse_mesh_spec

    with pytest.raises(ValueError) as ei:
        parse_shape_buckets("3.5:bad")
    assert "FGUMI_TPU_SHAPE_BUCKETS='3.5:bad'" in str(ei.value)
    assert "expected GROWTH[:CAP]" in str(ei.value)
    with pytest.raises(MeshConfigError) as ei:
        parse_mesh_spec("dp4xsp0")
    assert "FGUMI_TPU_MESH='dp4xsp0'" in str(ei.value)
    assert "expected 'auto', 'off', or 'dpNxspM'" in str(ei.value)
    with pytest.raises(ProfileError) as ei:
        validate_profile(_profile(knobs={"feeder_depth": 0}))
    assert "profile:knobs.feeder_depth=0" in str(ei.value)
    assert "expected an integer >= 2" in str(ei.value)


# ------------------------------------------------------------- precedence


def test_profile_fills_unset_knobs(tmp_path):
    rec = profmod.apply_profile(_profile(), path="p")
    assert sorted(rec["applied"]) == ["coalesce_window_ms", "feeder_depth"]
    assert os.environ["FGUMI_TPU_FEEDER_DEPTH"] == "3"
    assert os.environ["FGUMI_TPU_COALESCE_WINDOW_MS"] == "5.0"


def test_explicit_env_wins_over_profile(monkeypatch):
    monkeypatch.setenv("FGUMI_TPU_FEEDER_DEPTH", "7")
    rec = profmod.apply_profile(_profile(), path="p")
    assert "feeder_depth" in rec["skipped_explicit"]
    assert os.environ["FGUMI_TPU_FEEDER_DEPTH"] == "7"
    # the unset knob is still filled
    assert os.environ["FGUMI_TPU_COALESCE_WINDOW_MS"] == "5.0"


@pytest.mark.parametrize("knob, env, value", [
    ("feeder_depth", "FGUMI_TPU_FEEDER_DEPTH", 4),
    ("feeder_bytes", "FGUMI_TPU_FEEDER_BYTES", 64 << 20),
    ("shape_buckets", "FGUMI_TPU_SHAPE_BUCKETS", "1.25:4096"),
    ("chain_bytes", "FGUMI_TPU_CHAIN_BYTES", 1 << 20),
    ("coalesce_window_ms", "FGUMI_TPU_COALESCE_WINDOW_MS", 3.5),
    ("mesh", "FGUMI_TPU_MESH", "dp2xsp1"),
])
def test_precedence_per_knob(monkeypatch, knob, env, value):
    """Explicit env > profile > default, for every mapped knob."""
    prof = _profile(knobs={knob: value})
    monkeypatch.setenv(env, "sentinel")
    rec = profmod.apply_profile(prof, path="p")
    assert rec["skipped_explicit"] == [knob]
    assert os.environ[env] == "sentinel"
    profmod.reset_applied_for_tests()
    monkeypatch.delenv(env)
    rec = profmod.apply_profile(prof, path="p")
    assert rec["applied"] == [knob]
    assert os.environ[env] == str(value)


def test_application_is_process_once():
    rec1 = profmod.apply_profile(_profile(), path="first")
    rec2 = profmod.apply_profile(_profile(knobs={"mesh": "auto"}),
                                 path="second")
    assert rec2 is rec1
    assert "FGUMI_TPU_MESH" not in os.environ


# -------------------------------------------------------- prior seeding


def _auto_kernel():
    class K:
        @staticmethod
        def hybrid_mode():
            return True

    return K()


def test_cold_and_seeded_router_first_batch():
    """A cold router has never timed the device, so its static priors do
    not get to price the device out: the first batch goes to the device
    as an unmeasured-side probe. A profile-seeded router counts as
    measured and decides its first batch on cost, either way."""
    pytest.importorskip("fgumi_tpu.native.batch")
    from fgumi_tpu.native import batch as nb

    if not nb.available():
        pytest.skip("native engine unavailable")
    cold = OffloadRouter()
    # fam-3 shape: 4000 families x 3 reads x L=100
    shape = dict(n_rows=12000, n_segments=4000, L=100)
    assert cold.decide_batch(_auto_kernel(), **shape) == "device"
    snap = cold.snapshot()
    assert snap["prior_source"] == "cold"
    assert snap["last_decision"]["why"] == "probe-unmeasured"
    # on the priors alone the model would have said host
    pred = snap["last_decision"]
    assert pred["pred_device_s"] > pred["pred_host_s"]

    seeded = OffloadRouter()
    assert seeded.seed_priors({
        "link_mbps": 5000.0, "overhead_s": 0.001, "dispatch_wall_s": 0.001,
        "host_mcells_per_s": 5.0}, source="profile")
    assert seeded.decide_batch(_auto_kernel(), **shape) == "device"
    snap = seeded.snapshot()
    assert snap["prior_source"] == "profile"
    assert snap["last_decision"]["why"] == "cost"

    slow = OffloadRouter()
    assert slow.seed_priors({
        "link_mbps": 1.0, "overhead_s": 1.0, "dispatch_wall_s": 1.0,
        "host_mcells_per_s": 500.0}, source="profile")
    assert slow.decide_batch(_auto_kernel(), **shape) == "host"
    assert slow.snapshot()["last_decision"]["why"] == "cost"


def test_seeding_is_cold_only():
    r = OffloadRouter()
    r.observe_host(1_000_000, 0.1)  # measured: 10 Mcells/s
    assert not r.seed_priors({"host_mcells_per_s": 999.0})
    assert r.snapshot()["host_mcells_per_s"] == 10.0
    assert r.snapshot()["prior_source"] == "cold"


#: what the chooser priors / chooser state looked like in files written
#: before the device strand combines went
_OLD_CHOOSERS = {"duplex_combine": {"device_s_per_mcell": 0.001,
                                    "host_s_per_mcell": 0.004},
                 "codec_combine": {"device_s_per_mcell": 0.002}}


@pytest.mark.parametrize("kind", ["profile", "routing_state"])
def test_old_file_with_choosers_loads(tmp_path, caplog, kind):
    """A profile or a daemon routing-state file carrying a ``choosers``
    section (outside input, written before the device strand combines
    were deleted) loads: the section is ignored with one log line naming
    it, and the router half still seeds."""
    from fgumi_tpu.ops import router as router_mod

    if kind == "profile":
        prof = _profile()
        prof["priors"]["choosers"] = _OLD_CHOOSERS
        path = tmp_path / "old_profile.json"
        path.write_text(json.dumps(prof))
        with caplog.at_level(logging.INFO, logger="fgumi_tpu"):
            rec = profmod.apply_profile(load_profile(str(path)),
                                        path=str(path))
        assert rec["seeded_router"] is True
    else:
        from fgumi_tpu.serve.daemon import JobService

        journal = str(tmp_path / "old.journal")
        with open(journal + ".routing.json", "w") as fh:
            json.dump({"schema_version":
                       JobService.ROUTING_STATE_SCHEMA_VERSION,
                       "saved_unix": 1700000000,
                       "router": {"host_cps": {"value": 2e8, "samples": 3}},
                       "choosers": {name: {"device": {"value": v,
                                                      "samples": 2}}
                                    for name, v in (("duplex_combine", 1e-9),
                                                    ("codec_combine", 2e-9))}},
                      fh)
        svc = JobService(str(tmp_path / "old.sock"), workers=1,
                         journal_path=journal)
        with caplog.at_level(logging.INFO, logger="fgumi_tpu"):
            svc.start()
        try:
            assert svc.routing_state["loaded"] is True
            assert router_mod.ROUTER.snapshot()["host_mcells_per_s"] \
                == pytest.approx(200.0)
        finally:
            svc.close()
        router_mod.ROUTER.reset()
    said = [r.getMessage() for r in caplog.records
            if "choosers ignored" in r.getMessage()]
    assert len(said) == 1 and "choosers" in said[0]


def test_router_state_round_trip():
    r = OffloadRouter()
    r.observe_device(1 << 20, 4096, 0.01, 0.004, 0.02, devices=1)
    r.observe_device(1 << 20, 4096, 0.01, 0.004, 0.02, devices=4)
    r.observe_host(500_000, 0.01)
    r.observe_filter_keep(70, 100)
    state = json.loads(json.dumps(r.export_state()))  # wire-safe
    r2 = OffloadRouter()
    assert r2.restore_state(state, source="snapshot")
    assert r2.snapshot()["prior_source"] == "snapshot"
    s1, s2 = r.snapshot(), r2.snapshot()
    for k in ("link_mbps", "overhead_s", "dispatch_wall_s",
              "host_mcells_per_s", "filter_keep_rate"):
        assert s1[k] == s2[k], k
    assert s2["mesh"]["4"]["link_mbps"] == s1["mesh"]["4"]["link_mbps"]
    # restore is cold-only too
    r2.observe_host(1_000_000, 0.1)
    before = r2.snapshot()["host_mcells_per_s"]
    assert not r2.restore_state(state)
    assert r2.snapshot()["host_mcells_per_s"] == before


# ------------------------------------------------- fingerprint mismatch


def test_fingerprint_mismatch_warns_but_loads(caplog):
    fp = fingerprint_host()
    fp["cpu_count"] = (fp.get("cpu_count") or 1) + 64
    prof = _profile(fingerprint=fp)
    with caplog.at_level(logging.WARNING, logger="fgumi_tpu"):
        rec = profmod.apply_profile(prof, path="elsewhere.json")
    assert any("DIFFERENT hardware" in r.message for r in caplog.records)
    assert rec["fingerprint_mismatch"]
    assert rec["fingerprint_mismatch"][0]["field"] == "cpu_count"
    # the profile still applied
    assert "feeder_depth" in rec["applied"]


def test_matching_fingerprint_is_silent(caplog):
    with caplog.at_level(logging.WARNING, logger="fgumi_tpu"):
        rec = profmod.apply_profile(_profile(), path="here.json")
    assert not rec["fingerprint_mismatch"]
    assert not any("DIFFERENT hardware" in r.message
                   for r in caplog.records)


# ------------------------------------------------------ report + metrics


def test_profile_section_rides_run_report():
    from fgumi_tpu.observe.report import build_report, validate_report

    profmod.apply_profile(_profile(), path="prof.json")
    report = build_report("sort", ["sort"], 0.0, 0.1, 0)
    assert validate_report(report) == []
    sec = report["profile"]
    assert sec["path"] == "prof.json"
    assert "feeder_depth" in sec["knobs_applied"]
    assert sec["seeded_router"] is True
    assert "seeded_choosers" not in sec


def test_stamp_metrics_in_current_registry():
    from fgumi_tpu.observe.metrics import METRICS

    profmod.apply_profile(_profile(), path="p")
    profmod.stamp_metrics()
    snap = METRICS.snapshot()
    assert snap["tune.profile.loaded"] == 1
    assert snap["tune.profile.knobs_applied"] == 2
    assert snap["tune.profile.fingerprint_mismatch"] == 0


# ------------------------------------------------------ autotune / replay


def test_derive_from_replay_merges_evidence(tmp_path):
    from fgumi_tpu.tune.autotune import derive_from_replay

    report = {"device": {"routing": {
        "link_mbps": 100.0, "overhead_s": 0.02, "dispatch_wall_s": 0.03,
        "host_mcells_per_s": 40.0}}}
    report2 = {"device": {"routing": {
        "link_mbps": 200.0, "overhead_s": 0.04, "dispatch_wall_s": 0.05,
        "host_mcells_per_s": 60.0}}}
    micro = {"tune_cells": [
        {"name": "fixed3_L100", "distribution": "fixed", "mean_depth": 3,
         "read_length": 100, "backend": "cpu",
         "device_rows_per_sec": 1000.0, "host_rows_per_sec": 4000.0,
         "winner": "host"}]}
    paths = []
    for i, doc in enumerate((report, report2, micro)):
        p = tmp_path / f"in{i}.json"
        p.write_text(json.dumps(doc))
        paths.append(str(p))
    cells, router = derive_from_replay(paths)
    assert len(cells) == 1
    assert router["link_mbps"] == 150.0  # median of 100/200
    assert router["host_mcells_per_s"] == 50.0


def test_replay_rejects_unreadable_input(tmp_path):
    from fgumi_tpu.tune.autotune import derive_from_replay

    with pytest.raises(ProfileError, match="--replay"):
        derive_from_replay([str(tmp_path / "missing.json")])


def test_crossover_interpolation():
    from fgumi_tpu.tune.autotune import _crossover_depths

    cells = [
        {"name": "a", "distribution": "fixed", "mean_depth": 3,
         "read_length": 100, "device_rows_per_sec": 500.0,
         "host_rows_per_sec": 1000.0, "winner": "host"},
        {"name": "b", "distribution": "fixed", "mean_depth": 30,
         "read_length": 100, "device_rows_per_sec": 2000.0,
         "host_rows_per_sec": 1000.0, "winner": "device"},
    ]
    cross = _crossover_depths(cells)["fixed_L100"]
    assert cross["winner_below"] == "host"
    assert cross["winner_above"] == "device"
    assert 3 < cross["crossover_depth"] < 30


def test_tune_quick_cli_produces_valid_artifacts(tmp_path):
    """`fgumi-tpu tune --quick` end to end: schema-valid profile + atlas
    (the CI smoke re-runs this against the committed artifacts)."""
    pytest.importorskip("jax")
    from fgumi_tpu.cli import main as cli_main

    prof_path = tmp_path / "prof.json"
    atlas_path = tmp_path / "atlas.json"
    rc = cli_main(["tune", "--quick", "-o", str(prof_path),
                   "--atlas", str(atlas_path)])
    assert rc == 0
    prof = load_profile(str(prof_path))
    assert prof["source"] == "autotune"
    assert prof["quick"] is True
    atlas = json.loads(atlas_path.read_text())
    assert atlas["kind"] == "fgumi-tpu-crossover-atlas"
    assert len(atlas["cells"]) == 3
    for cell in atlas["cells"]:
        assert cell["device_rows_per_sec"] > 0


def test_bad_profile_is_exit_2(tmp_path, monkeypatch):
    from fgumi_tpu.cli import main as cli_main

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"schema_version": 1}))
    monkeypatch.setenv("FGUMI_TPU_PROFILE", str(bad))
    rc = cli_main(["--profile", str(bad), "stats",
                   "--socket", str(tmp_path / "none.sock")])
    assert rc == 2
