"""Device, whole engine step: model FLOPs over step time at peak, in %.

Numerator: the model operations of the tokens the engine steps of the
window processed (``bench/flops.py``: 2 x layer parameters per prompt
token, attention over each token's causal context, and the output head
for every decoded token; the head of a prefill chunk's last token is
left out, so the share is a floor).  Denominator: the summed wall time
of those ``core.step`` spans (host clock) times the chip's bf16 peak.
Its denominator is step time, not the window, so it moves in an open
loop too.
"""

from bench.spans import DECODE, PREFILL, STEP


def read(ctx):
    peaks = ctx["peaks"]
    if peaks is None:
        return None
    fl, conf, sp = ctx["flops"], ctx["config"], ctx["spans"]
    t0, t1 = ctx["window"]
    steps = [s for s in sp.between(STEP, t0, t1) if s.info.get("work")]
    wall = sum(s.t1 - s.t0 for s in steps)
    if wall <= 0:
        return None
    lo, hi = steps[0].t0, steps[-1].t1
    work = sum(fl.prefill_chunk_flops(conf, s.info["start"], s.info["n"],
                                      last=False)
               for s in sp.between(PREFILL, lo, hi))
    work += sum(fl.decode_step_flops(conf, s.info["kv_lens"])
                for s in sp.between(DECODE, lo, hi))
    return 100.0 * work / (wall * float(peaks["bf16_flops_per_s"]))
