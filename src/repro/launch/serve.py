"""Serving launcher: batched on-demand inference.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --reduced \
        --requests 8 --max-new 32
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import ALIASES, get_config
from repro.configs.reduced import reduce_config
from repro.models import init_params
from repro.serving import Request, ServeEngine


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=256)
    args = ap.parse_args(argv)

    cfg = get_config(ALIASES.get(args.arch, args.arch))
    if args.reduced:
        cfg = reduce_config(cfg)
    print(f"arch={cfg.name} params~{cfg.param_count()/1e6:.0f}M")
    params = init_params(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(cfg, params, max_seq=args.max_seq)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, args.prompt_len,
                                        dtype=np.int32),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]
    t0 = time.time()
    engine.serve_batch(reqs)
    dt = time.time() - t0
    n = sum(len(r.tokens_out) for r in reqs)
    print(f"{n} tokens / {len(reqs)} requests in {dt:.2f}s "
          f"({n/dt:.1f} tok/s)")
    for r in reqs[:3]:
        print(f"  req {r.rid}: ttfb={1e3*(r.first_token_at-r.submitted_at):.0f}ms "
              f"tokens={r.tokens_out[:8]}...")


if __name__ == "__main__":
    main()
