"""Serve the paper's CNNs -- AlexNet/VGG16/VGG19 -- through the batched engine.

The CNN serving path (DESIGN.md section 9) in one script:

  * ``get_config("alexnet")`` (or ``vgg16`` / ``vgg19``) resolves the CNN
    from the same registry as the transformer archs; ``reduced(cfg)`` shrinks
    it to CPU-demo size with the full layer topology intact.
  * Under an integer KOM policy the engine quantizes every conv/FC weight
    ONCE at build (int16 values + per-output-channel scales); each serving
    step quantizes activations only, with per-row scales, so a request's
    logits never depend on its batch-mates.
  * A mixed-size stream of image requests drains through fixed batch
    buckets (here 1/4/8): each microbatch is zero-padded to a bucket shape,
    so after the first pass per bucket every jit lookup is a cache hit.
  * ``engine.stats()`` reports images/sec, p95 latency and the padding
    overhead -- the serving-side counterpart of the per-layer cost rows in
    ``benchmarks/table_convnets.py``.

  * ``--explore`` prints the per-layer plan summary before serving --
    one line per conv geometry with the chosen engine, tile block and
    epilogue ``fusion`` (``bias_relu`` / ``pool`` / ``pool_quant``, the
    cross-layer fused dataflow of DESIGN.md section 7.7); add
    ``--requant`` to let the explorer pick the pool_quant handoff.

Run:  PYTHONPATH=src python examples/serve_cnn.py
      PYTHONPATH=src python examples/serve_cnn.py --arch vgg16 --requests 12
      PYTHONPATH=src python examples/serve_cnn.py --arch alexnet \\
          --policy kom_int14 --conv-path im2col --buckets 1,4,8
      PYTHONPATH=src python examples/serve_cnn.py --arch vgg16 \\
          --policy kom_int14 --explore --model-only --requant
"""
import sys

from repro.launch.serve import main

if __name__ == "__main__":
    # --reduced: the CPU demo serves the small twin; the published widths
    # run on the chip (chip_smoke.py).
    argv = ["--reduced"] + (sys.argv[1:] or [
        "--arch", "alexnet", "--policy", "kom_int14", "--requests", "10",
        "--buckets", "1,4,8"])
    sys.exit(main(argv))
