"""The general generator: layouts found by name, inputs from the seed alone
(CPU, no program)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import traffic  # noqa: E402

LAYOUT = '''
def generate(params, rng, common):
    return {"marks": rng.integers(0, 9, len(common["fam"])),
            "n_reads": len(common["fam"])}


def write(data, prefix, level):
    with open(prefix + ".txt", "w") as f:
        f.write(" ".join(map(str, data["marks"])))
    return [prefix + ".txt"]
'''


def test_a_new_kind_of_input_is_one_new_file(tmp_path, monkeypatch):
    os.makedirs(tmp_path / "traffic")
    (tmp_path / "traffic" / "marks.py").write_text(LAYOUT)
    (tmp_path / "traffic" / "few.json").write_text(
        '{"kind": "marks", "num_families": 7, "family_size": 3}')
    monkeypatch.setattr(traffic, "ROOT", str(tmp_path))
    params = traffic.load("few", str(tmp_path))
    data = traffic.generate(params, 2147483659)
    assert data["kind"] == "marks" and data["n_reads"] == 21
    paths = traffic.write_inputs(data, str(tmp_path / "input"))
    assert paths == [str(tmp_path / "input.txt")] and os.path.exists(paths[0])


def test_an_unknown_kind_is_an_error():
    with pytest.raises(ValueError, match="unknown traffic kind 'nosuch'"):
        traffic.generate({"kind": "nosuch", "num_families": 3,
                          "family_size": 2,
                          "family_size_distribution": "fixed"}, 1)


@pytest.mark.parametrize("name", ["lognormal5", "longtail", "pairs5"])
def test_seeds_permute_one_multiset_of_sizes(name):
    params = traffic.load(name, traffic.ROOT)
    params["num_families"] = 400
    a = traffic.generate(params, 11)
    b = traffic.generate(params, 11)
    c = traffic.generate(params, 3000000019)
    assert all(np.array_equal(a[k], b[k]) for k in a if k != "kind")
    assert a["n_reads"] == c["n_reads"]
    assert np.array_equal(np.sort(a["sizes"]), np.sort(c["sizes"]))
    assert not np.array_equal(a["codes1"], c["codes1"])
