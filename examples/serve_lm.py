"""End-to-end driver: serve a small model with batched requests through the
continuous-batching engine (prefill + KV-cache decode).

Run:  PYTHONPATH=src python examples/serve_lm.py
      PYTHONPATH=src python examples/serve_lm.py --arch olmoe-1b-7b --requests 8
"""
import sys

from repro.launch.serve import main

if __name__ == "__main__":
    argv = ["--reduced"] + (sys.argv[1:] or [
        "--arch", "granite-3-2b", "--requests", "6", "--slots", "3",
        "--max-new", "10"])
    sys.exit(main(argv))
