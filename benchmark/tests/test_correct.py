"""``correct`` has to be able to come out false (CPU; needs the program).

1. The control: the float32 reference in the program's place fails the
   comparison, in both configurations, at a size a test run can hold.
2. A whole run of the harness (the look for a chip skipped by ``--rehearse``)
   with the timed path broken underneath: a sound run is correct, and each
   fault a cell can have makes it incorrect: an answer altered where it is
   produced, half of the records left out, an output never committed.
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.dirname(ROOT)]

import pytest  # noqa: E402

import bamio  # noqa: E402
import control  # noqa: E402
import run as harness  # noqa: E402

CELLS = ["simplex-c1.lognormal5", "chain-c5.pairs5"]


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [7, 2147483659])
def test_control_in_float32_is_not_correct(workload, seed, tmp_path):
    verdict = control.control(workload, seed, families=1500,
                              work=str(tmp_path))
    assert verdict["correct"] is False
    assert verdict["compared"]["records_differing"]["value"] > 0


def _alter(path):
    payload = bytearray(bamio.read_bgzf(path))
    _text, start = bamio.split_bam(bytes(payload))
    offs = bamio.record_offsets(bytes(payload), start)
    mid = int(offs[len(offs) // 2])
    l_name = payload[mid + 12]
    l_seq = int.from_bytes(payload[mid + 20:mid + 24], "little")
    qual = mid + 36 + l_name + (l_seq + 1) // 2 + l_seq // 2
    payload[qual] ^= 1  # one quality of one read, by one
    bamio.write_bgzf(path, bytes(payload))


def _halve(path):
    payload = bamio.read_bgzf(path)
    _text, start = bamio.split_bam(payload)
    offs = bamio.record_offsets(payload, start)
    bamio.write_bgzf(path, payload[:int(offs[len(offs) // 2])])


FAULTS = {"sound": None, "answer_altered": _alter, "half_left_out": _halve,
          "never_committed": os.remove}


@pytest.mark.parametrize("fault", list(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_a_broken_timed_path_is_seen(workload, fault, monkeypatch, capsys):
    import fgumi_tpu.cli as cli

    real = cli.main

    def broken(argv):
        rc = real(argv)
        out = argv[argv.index("-o") + 1]
        if FAULTS[fault] and os.path.basename(out).startswith("job"):
            FAULTS[fault](out)
        return rc

    monkeypatch.setattr(cli, "main", broken)
    rc = harness.main(["--workload", workload, "--seed", "11", "--seconds",
                       "0.2", "--trace", "0", "--rehearse"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["rehearsal"] and result["attempted"] >= 1
    assert result["correct"] is (fault == "sound")
    assert list(result)[-1] == "compared"
    bad = {k for k, v in result["compared"].items() if v["value"] > v["limit"]}
    assert bad == {"sound": set(), "answer_altered": {"records_differing"},
                   "half_left_out": {"record_count_gap"},
                   "never_committed": {"jobs_failed"}}[fault]
