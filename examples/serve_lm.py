"""Batched serving example: prefill + ring-buffer decode.

    PYTHONPATH=src python examples/serve_lm.py --arch gemma2_2b --tokens 24

Uses the reduced (smoke) config of the chosen architecture so it runs on
CPU; the sliding-window layers keep bounded ring-buffer KV caches (the
sequence shift buffer) while global layers keep full caches.
"""

import argparse

import jax
import numpy as np

from repro.configs import ARCHS, get_smoke
from repro.models import init_lm
from repro.models import ServeEngine
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2_2b",
                    choices=[a for a in ARCHS if a != "whisper_small"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.8)
    args = ap.parse_args()

    cfg = get_smoke(args.arch)
    params = init_lm(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, batch=args.batch, max_len=256,
                         temperature=args.temperature)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab, (args.batch, 12)).astype(np.int32)
    out = engine.generate(prompts, max_new_tokens=args.tokens, seed=1)
    for i, row in enumerate(out):
        print(f"seq {i}: {row.tolist()}")
    print(f"decoded {engine.stats.decode_tokens} tokens "
          f"(prefill {engine.stats.prefill_tokens})")
    print("serve_lm OK")


if __name__ == "__main__":
    main()
