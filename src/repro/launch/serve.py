"""Serving launcher: continuous-batching decode over the paged-KV engine.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
      --requests 12 --max-new 24

The engine exercises the paper's §2.2 path end-to-end: page allocation goes
through RDMA buffer registration, virtual->physical page translation hits
the (software) TLB, and decode attention dispatches through the paged-
attention kernel whose in-kernel page-table lookup is the hardware-TLB
analogue.  Engine stats report the TLB hit rate and translation cost next
to throughput.
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--page-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import numpy as np  # noqa: E402
    import jax  # noqa: E402

    from repro import configs  # noqa: E402
    from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
    from repro.models import api  # noqa: E402
    from repro.serving.engine import Engine, PagedLM, Request  # noqa: E402

    enable_compile_cache()

    cfg = configs.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.family not in ("dense", "moe", "vlm"):
        print(f"[serve] family {cfg.family} has no paged-KV decode "
              "(O(1) recurrent state) — engine targets transformer archs")
        return 2

    model = api.get_model(cfg)
    params = model.init(jax.random.key(args.seed))
    max_seq = args.prompt_len + args.max_new + args.page_tokens
    lm = PagedLM(cfg, params, max_batch=args.max_batch, max_seq=max_seq,
                 page_tokens=args.page_tokens)
    eng = Engine(lm)
    rng = np.random.default_rng(args.seed)
    for rid in range(args.requests):
        plen = int(rng.integers(4, args.prompt_len + 1))
        prompt = rng.integers(0, cfg.vocab, size=(plen,)).astype(np.int32)
        eng.submit(Request(rid=rid, prompt=prompt,
                           max_new_tokens=args.max_new))
    t0 = time.perf_counter()
    eng.run_to_completion()
    dt = time.perf_counter() - t0
    stats = eng.stats()
    toks = sum(len(r.out_tokens) for r in eng.finished)
    print(f"[serve] arch={cfg.name} requests={len(eng.finished)} "
          f"tokens={toks} wall={dt:.2f}s ({toks/dt:.1f} tok/s)")
    print(f"[serve] decode_steps={stats['decode_steps']} "
          f"tlb_hit_rate={stats['tlb_hit_rate']:.3f} "
          f"translation_cost={stats['translation_cost_s']*1e6:.1f} us")
    assert len(eng.finished) == args.requests
    return 0


if __name__ == "__main__":
    sys.exit(main())
