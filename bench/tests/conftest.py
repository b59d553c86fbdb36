"""Put ``bench/`` (the harness's flat modules) and ``src/`` on the path."""
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def tiny_spec(tmp_path):
    """BENCHMARK.json with each configuration cut to a CPU-test size by its
    family's ``tiny``.  Each keeps its full-size configuration's correctness
    limit."""
    import json

    import family

    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for entry in spec["configs"]:
        full = json.loads((BENCH.parent / entry["file"]).read_text())
        path = tmp_path / f"{entry['name']}.json"
        path.write_text(json.dumps(family.of(full).tiny(full)))
        entry["file"] = str(path)
    return spec


def pytest_configure(config):
    # CPU runs here gain nothing from the harness's persistent compile cache
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
