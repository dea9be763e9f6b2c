"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests the engine
finished — drawn from the seed, always holding the one with the most
served tokens, one whose prompt fit one prefill chunk and one whose
prompt did not — is run through the plain reference, one forward over
each prompt and its served tokens.  At every served token the
reference's best logit minus its logit for the served token is the
token's gap; the number compared is the widest gap.  Greedy serving
that computes what the configuration states gives only rounding-sized
gaps; a wrong token, a wrong path or a lower precision gives gaps the
size of the logits' spread.

The control (``prec="int8"``) is the reference one precision step below
the configuration's, put in the program's place: at the same positions
its own best token is scored by the float32 reference the same way.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path
from typing import Dict, List, Mapping, Sequence

import numpy as np

from bench import weights as bench_weights
from bench.weights import seed32

REFERENCE_DIR = Path(__file__).resolve().parent / "reference"


def load_reference(name: str, root: Path = REFERENCE_DIR):
    path = root / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_reference_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sample(finished: Sequence, seed: int, *, tokens: int,
           chunk: int) -> List:
    """Records to compare: the one with most served tokens, the longest
    prompt that fit one chunk and the longest that did not, then others
    in an order drawn from the seed until ``tokens`` served tokens."""
    recs = [r for r in finished if r.tokens]
    if not recs:
        return []
    rng = np.random.default_rng(seed32(seed, "check"))
    order = [recs[i] for i in rng.permutation(len(recs))]
    picked = [max(recs, key=lambda r: (len(r.tokens), len(r.req.prompt)))]
    for fits in (True, False):
        kind = [r for r in recs if (len(r.req.prompt) <= chunk) == fits]
        if kind:
            picked.append(max(kind, key=lambda r: len(r.req.prompt)))
    out = []
    for r in picked + order:
        if sum(len(p.tokens) for p in out) >= tokens and len(out) >= len(
                picked):
            break
        if all(r is not p for p in out):
            out.append(r)
    return out


def gaps(conf: Mapping, params, ref, prompt: Sequence[int],
         served: Sequence[int], *, width: int, rows: int,
         control: bool = False) -> Dict[str, np.ndarray]:
    """Per served token: the reference's gap to its best logit; with
    ``control`` also the gap of the int8 control's best token."""
    import jax.numpy as jnp
    n = len(prompt) + len(served)
    if n > width or len(served) > rows:
        raise ValueError(f"sequence {n} / {len(served)} served tokens over "
                         f"the reference's shape {width} / {rows}")
    seq = np.zeros(width, np.int32)
    seq[:n] = list(prompt) + list(served)
    at = np.zeros(rows, np.int32)
    at[:len(served)] = np.arange(len(prompt) - 1, n - 1)
    tok = np.zeros(rows, np.int32)
    tok[:len(served)] = served
    lg = ref.logits_at(params, conf, seq, at, prec="f32")
    best = jnp.max(lg, -1)
    out = {"served": np.asarray(
        best - jnp.take_along_axis(lg, jnp.asarray(tok)[:, None], -1)[:, 0]
    )[:len(served)]}
    if control:
        cl = ref.logits_at(params, conf, seq, at, prec="int8")
        pick = jnp.argmax(cl, -1)
        out["control"] = np.asarray(
            best - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]
        )[:len(served)]
    return out


def compare(conf: Mapping, seed: int, records: Sequence, *, width: int,
            rows: int, control: bool = False, params=None
            ) -> Dict[str, float]:
    """Widest gap over ``records`` (and the control's), with the count
    of served tokens compared.  The weights are made anew from the seed
    unless ``params`` hands in the same seed's weights."""
    if not records:
        return {"logit_gap_max": None, "tokens": 0, "requests": 0}
    ref = load_reference(conf["reference"])
    if params is None:
        params = bench_weights.make(conf, seed)
    served, ctrl = [], []
    for r in records:
        g = gaps(conf, params, ref, r.req.prompt, r.tokens, width=width,
                 rows=rows, control=control)
        served.append(g["served"])
        if control:
            ctrl.append(g["control"])
    out = {"logit_gap_max": float(np.max(np.concatenate(served))),
           "tokens": int(sum(len(s) for s in served)),
           "requests": len(records)}
    if control:
        out["control_gap_max"] = float(np.max(np.concatenate(ctrl)))
    return out
