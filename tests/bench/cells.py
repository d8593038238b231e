"""Tiny stand-ins of each benchmark cell for the CPU: same drivers, same
checks, smoke-size model and short traffic."""
import jax.numpy as jnp

TINY_MODEL = dict(n_layers=2, d_model=64, n_heads=4, d_head=16, d_ff=96, vocab_size=300)


def shrink(spec: dict) -> None:
    """``run_cell`` override: smoke-size widths, 4 slots, short prompts."""
    m = spec["config"]["model"]
    spec["config"] = dict(spec["config"], model=dict(
        m, **TINY_MODEL, n_kv_heads=min(m["n_kv_heads"], 2)),
        scheduler={"n_slots": 4, "page_size": 16, "max_prefill_batch": 2})
    mix = dict(spec["traffic"])
    if mix["driver"] == "serve":
        mix.update(prompt=dict(mix["prompt"], min=4, median=12, max=40),
                   output=dict(mix["output"], min=3, median=6, max=12),
                   ramp_s=0.5, drain_s=20, trace_s=0.5)
        mix["check"] = dict(mix["check"], requests=3, min_tokens=8)
        if mix["loop"] == "open":
            mix["rate_per_s"] = 6.0
        else:
            mix["backlog"] = 40
    else:
        mix.update(inputs=2, check_calls=4)
    spec["traffic"] = mix


def alter_tokens(stage, obj):
    """Fault: every decoded token is altered where the engine produces it."""
    if stage == "serve.engine":
        orig = obj._run_decode

        def run_decode():
            before = {s: len(r.tokens) for s, r in obj.active.items()}
            reqs = dict(obj.active)
            orig()
            for s, r in reqs.items():
                if len(r.tokens) > before[s]:
                    r.tokens[-1] = (r.tokens[-1] + 1) % obj.cfg.vocab_size

        obj._run_decode = run_decode
        return obj
    if stage == "sampler.call":
        def call(*a):
            vals, toks = obj(*a)
            return vals.at[0].add(jnp.asarray(1, vals.dtype)), (toks + 1) % 7
        return call
    return obj


def flaky_decode(stage, obj):
    """Fault: every 8th decode launch raises once before it runs; the
    engine's retry runs it again and the request goes on."""
    if stage != "serve.engine":
        return obj
    orig = obj._decode_fn
    calls = [0]

    def decode_fn(sig):
        fn = orig(sig)

        def launch(*a):
            calls[0] += 1
            if calls[0] % 8 == 0:
                raise RuntimeError("injected launch failure")
            return fn(*a)

        return launch

    obj._decode_fn = decode_fn
    return obj
