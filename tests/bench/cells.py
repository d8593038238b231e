"""Tiny stand-ins of each benchmark cell for the CPU: same drivers, same
checks, smoke-size model and short traffic. The cells and configurations
are those ``BENCHMARK.json`` lists."""
import jax.numpy as jnp

from bench import run as bench_run

TINY_MODEL = dict(n_layers=2, d_model=64, n_heads=4, d_head=16, d_ff=96, vocab_size=300)
#: smoke widths of the nested groups, as ``repro.configs.registry.get_smoke_config`` sets them
TINY_MLA = dict(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)
TINY_MOE = dict(n_experts=8, top_k=2, d_expert=32, router_block=4)

_BENCH = bench_run.load_benchmark()
_SPECS = {w["name"]: bench_run.resolve(_BENCH, w["name"]) for w in _BENCH["workloads"]}
#: every cell, in BENCHMARK.json's order
CELLS = list(_SPECS)


def cells_of(driver: str) -> list:
    """The cells whose traffic mix names ``driver`` (``serve``: serving cells)."""
    return [c for c, spec in _SPECS.items() if spec["driver"] == driver]


def served_configs() -> list:
    """The configurations some serving cell runs, each once."""
    return list(dict.fromkeys(_SPECS[c]["cell"]["config"] for c in cells_of("serve")))


def config_entry(name: str) -> dict:
    """The configuration file of ``name`` as BENCHMARK.json lists it."""
    return next(s["config"] for s in _SPECS.values() if s["cell"]["config"] == name)


def tiny_model(m: dict, **kw) -> dict:
    """A model block at smoke widths; its nested ``mla`` / ``moe`` groups are
    kept, at smoke widths too. ``kw`` overrides any key last."""
    out = dict(m, **TINY_MODEL)
    if m.get("mla"):
        out.update(mla=dict(m["mla"], **TINY_MLA), d_head=None)
    if m.get("moe"):
        out["moe"] = dict(m["moe"], **TINY_MOE,
                          n_shared_experts=min(m["moe"]["n_shared_experts"], 1))
    out.update(kw)
    return out


def shrink(spec: dict) -> None:
    """``run_cell`` override: smoke-size widths, 4 slots, short prompts."""
    m = spec["config"]["model"]
    spec["config"] = dict(spec["config"], model=tiny_model(m, n_kv_heads=min(m["n_kv_heads"], 2)),
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
