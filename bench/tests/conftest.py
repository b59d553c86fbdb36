"""Put ``bench/`` (the harness's flat modules) and ``src/`` on the path."""
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def tiny_spec(tmp_path):
    """BENCHMARK.json with each configuration cut to a CPU-test size.

    Same layer kinds, kernels, strides and pools; channels capped at 16, FC
    widths at 32, 16 classes, 32 px (VGG) and 67 px (AlexNet).  Each keeps
    its full-size configuration's correctness limit.
    """
    import copy
    import json

    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for entry in spec["configs"]:
        full = json.loads((BENCH.parent / entry["file"]).read_text())
        tiny = copy.deepcopy(full)
        layers = []
        for s in full["layers"]:
            if s[0] == "conv":
                layers.append(["conv", s[1], min(s[2], 16), s[3]])
            elif s[0] == "fc":
                layers.append(["fc", min(s[1], 32)])
            else:
                layers.append(s)
        layers[-1] = ["fc", 16]
        tiny.update(layers=layers, n_classes=16,
                    img_size=67 if full["first_conv_padding"] == "VALID" else 32)
        path = tmp_path / f"{entry['name']}.json"
        path.write_text(json.dumps(tiny))
        entry["file"] = str(path)
    return spec


def pytest_configure(config):
    # CPU runs here gain nothing from the harness's persistent compile cache
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
