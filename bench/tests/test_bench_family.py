"""Configurations find the module that knows their shape by name.

A network of a new shape is a configuration file and a family file
(``bench/families/<family>.py``) and nothing else: here a family written to
a temporary directory runs a whole cell through the harness.
"""
import json
import textwrap
import types

import pytest

import family
import run
import work
from conftest import BENCH, tiny_spec


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_configurations_without_the_key_are_chains():
    for name in ("vgg16", "alexnet"):
        assert "family" not in config(name)
        assert family.of(config(name)) is family.load("chain")
    assert "chain" in family.known()


def test_unknown_family_lists_the_known_ones():
    with pytest.raises(SystemExit, match=r"unknown family 'nosuch'.*'chain'"):
        family.of(dict(config("vgg16"), family="nosuch"))
    with pytest.raises(SystemExit, match="unknown family"):
        family.load("../family")


#: A family that runs the chain's code and records each call by name.
TOY = textwrap.dedent('''
    import family

    _chain = family.load("chain")
    CALLS = []


    def _recorded(name):
        def call(*args, **kw):
            CALLS.append(name)
            return getattr(_chain, name)(*args, **kw)
        return call


    for _name in ("init_params", "forward", "layers", "program_config",
                  "pallas_layers", "tiny"):
        globals()[_name] = _recorded(_name)
''')


def test_a_new_family_is_new_files_only(tmp_path, monkeypatch):
    spec = tiny_spec(tmp_path)
    fams = tmp_path / "families"
    fams.mkdir()
    (fams / "chain.py").symlink_to(family.DIR / "chain.py")
    (fams / "toy.py").write_text(TOY)
    entry = next(e for e in spec["configs"] if e["name"] == "alexnet")
    cfg = json.loads(open(entry["file"]).read())
    path = tmp_path / "alexnet_toy.json"
    path.write_text(json.dumps(dict(cfg, family="toy")))
    entry["file"] = str(path)
    monkeypatch.setattr(family, "DIR", fams)

    line, _ = run.run_cell(spec, "alexnet.sync1", 2**33 + 5, 0.5, False,
                           require_chip=False)
    assert line["correct"] is True, line["checks"]
    calls = set(family.load("toy").CALLS)
    assert {"init_params", "forward", "layers", "program_config",
            "pallas_layers"} <= calls


def test_pallas_layers_refuses_a_count_mismatch():
    chain = family.load("chain")
    vgg, alex = config("vgg16"), config("alexnet")
    engine = types.SimpleNamespace(cfg=chain.program_config(vgg, vgg["policy"]),
                                   plan=None)
    with pytest.raises(ValueError, match="5 conv layers.*13"):
        chain.pallas_layers(engine, alex)


@pytest.mark.parametrize("name,pallas", [
    ("vgg16", [1, 3, 4, 6, 7, 8, 10, 11, 12, 14, 15, 16]),
    ("alexnet", [2, 4, 5, 6]),
])
def test_chain_pallas_layers_under_the_tpu_plan(name, pallas):
    """Every conv but VGG16's RGB stem and AlexNet's 11x11/s4 stem goes to a
    Pallas engine under the plan a TPU resolves."""
    from repro.core.planner import resolve_plan

    c = config(name)
    chain = family.of(c)
    cfg = chain.program_config(c, c["policy"])
    engine = types.SimpleNamespace(cfg=cfg,
                                   plan=resolve_plan(cfg, backend="tpu"))
    got = chain.pallas_layers(engine, c)
    assert [l.index for l in got] == pallas
    assert all(l.kind is work.Kind.CONV for l in got)
