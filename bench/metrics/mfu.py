"""Model FLOPs of every token the window finished (each prompt's prefill
when its first token lands, each decoded token at its context length;
``bench/work.py``) over the window's length times the chip's bf16 peak, %."""


def read(rec: dict, name: str):
    w0, w1 = rec["w0"], rec["w1"]
    flops = sum(f for t, f in rec.get("flops", []) if w0 < t <= w1)
    if not flops:
        return None
    return 100.0 * flops / ((w1 - w0) / 1e9 * rec["peaks"]["bf16_flops"])
