"""Serving launcher: batched generation with LOMS top-k sampling.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
      --batch 4 --prompt-len 32 --new-tokens 16 --top-k 16

Ragged prompts (attention-cache families): ``--ragged`` draws a random
length per request in [1, prompt_len], right-pads the batch, and prefill
gathers each row's logits at its own last valid position — bit-identical
per row to running the unpadded prompt alone.

Engine mode: ``--engine`` routes the same request mix through the
continuous-batching scheduler (admission queue, paged KV-cache slots,
disaggregated prefill/decode) instead of one-shot ``generate()``. The
command exits non-zero, naming the error, when any request ends FAILED
or TIMED_OUT.
"""
from __future__ import annotations

import argparse
import math
import sys


def build_engine(params, cfg, *, slots: int, page_size: int,
                 prompt_len: int, new_tokens: int):
    """The scheduler ``--engine`` serves through: ``slots`` paged slots,
    each with pages for ``prompt_len + new_tokens`` positions."""
    from repro.serving.scheduler import ScheduledEngine, SchedulerConfig

    pages = math.ceil((prompt_len + new_tokens) / page_size)
    return ScheduledEngine(params, cfg, SchedulerConfig(
        n_slots=slots, page_size=page_size, pages_per_slot=pages))


def unfinished(eng) -> list:
    """``(rid, state, error)`` of every request that ended FAILED or
    TIMED_OUT."""
    from repro.serving.scheduler.request import RequestState

    bad = (RequestState.FAILED, RequestState.TIMED_OUT)
    return [(r.rid, r.state.value, r.error)
            for r in eng.requests.values() if r.state in bad]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--ragged", action="store_true",
                    help="random per-request prompt lengths in "
                         "[1, prompt_len], right-padded per bucket")
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--top-k", type=int, default=16)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--engine", action="store_true",
                    help="serve through the request scheduler "
                         "(paged slots + continuous batching)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.compile_cache import enable_compile_cache
    from repro.configs import get_config, get_smoke_config
    from repro.models import model_init
    from repro.serving.engine import ServeConfig, generate

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    assert not cfg.is_encoder_only, f"{cfg.name} is encoder-only: no decode"
    params, _ = model_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    lengths = None
    if args.ragged:
        assert cfg.family in ("dense", "moe"), \
            f"--ragged needs attention caches, not {cfg.family}"
        lengths = rng.integers(1, args.prompt_len + 1, args.batch).astype(np.int32)
    tokens = rng.integers(0, cfg.vocab_size,
                          (args.batch, args.prompt_len)).astype(np.int32)
    if lengths is not None:
        for r, n in enumerate(lengths):  # right-pad past each valid length
            tokens[r, n:] = 0

    if args.engine:
        from repro.serving.scheduler import SamplingParams

        assert cfg.family == "dense", \
            "--engine bit-equality contract covers dense stacks"
        lens = lengths if lengths is not None \
            else np.full(args.batch, args.prompt_len, np.int32)
        eng = build_engine(params, cfg, slots=args.slots,
                           page_size=args.page_size,
                           prompt_len=args.prompt_len,
                           new_tokens=args.new_tokens)
        rids = [eng.submit(tokens[r, :lens[r]],
                           SamplingParams(k=args.top_k, top_p=args.top_p,
                                          temperature=args.temperature,
                                          max_new_tokens=args.new_tokens,
                                          seed=r),
                           arrival=r)
                for r in range(args.batch)]
        out = eng.run()
        bad = unfinished(eng)
        if bad:
            for rid, state, err in bad:
                print(f"[serve] request {rid} {state}: {err}",
                      file=sys.stderr)
            sys.exit(f"[serve] {len(bad)} of {len(rids)} requests did not "
                     f"finish; first error: {bad[0][2]}")
        print(f"[serve] engine drained {len(out)} requests in {eng.t} ticks "
              f"({args.slots} slots, page {args.page_size})")
        for rid in rids[:2]:
            print(f"  rid {rid}: {out[rid]}")
        return

    batch = {"tokens": jnp.asarray(tokens)}
    if lengths is not None:
        batch["lengths"] = lengths
    if cfg.family == "vlm":
        batch["patches"] = jnp.asarray(
            rng.standard_normal((args.batch, cfg.frontend_len, cfg.frontend_dim)),
            jnp.float32)
    out = generate(params, batch, cfg,
                   ServeConfig(max_new_tokens=args.new_tokens, top_k=args.top_k,
                               top_p=args.top_p,
                               temperature=args.temperature))
    print(f"[serve] tokens shape {out['tokens'].shape} "
          f"prefill {out['prefill_s']*1e3:.1f}ms "
          f"decode {out['tok_per_s']:.1f} tok/s")
    print(out["tokens"][:2])


if __name__ == "__main__":
    main()
