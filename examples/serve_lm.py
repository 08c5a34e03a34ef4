"""LM serving through the ORCA engine: continuous batching, ring-buffer
admission, cpoll notification — clients inject prompts, the engine prefils
into free slots and decodes all active slots each tick.

    PYTHONPATH=src python examples/serve_lm.py --requests 16 --arch rwkv6-1.6b --reduced
"""
import sys

sys.path.insert(0, __file__.rsplit("/", 2)[0] + "/src")

import argparse

from repro.launch import serve as serve_mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--paged", action="store_true",
                    help="decode through the shared KV page pool")
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "pallas", "ref"))
    ap.add_argument("--reduced", action="store_true",
                    help="CPU test size of the config (2 layers, float32)")
    args = ap.parse_args()
    serve_mod.main([
        "--arch", args.arch,
        "--requests", str(args.requests),
        "--prompt-len", "12", "--gen-len", "8",
        "--backend", args.backend,
    ] + (["--paged"] if args.paged else [])
      + (["--reduced"] if args.reduced else []))


if __name__ == "__main__":
    main()
