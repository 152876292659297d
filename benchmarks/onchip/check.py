"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished, drawn from the seed and holding the
longest of them, is run once through the configuration's plain float32
reference (``references/<config>.py``), over each prompt followed by its
served tokens.  Every served token is greedy, so each should be the
reference's best next token up to rounding: the number compared is the
widest gap by which a served token's reference logit lies below the
reference's best logit at its position (``max_logit_gap``).

A control reads the same number for the reference itself computed one
precision step lower (``references/common.py``): at each position of the
same prompts and tokens, the gap of the token that the lower precision
puts first.  Controls run only when asked for (``calibrate.py``).  With
``control_as_program`` a control's first tokens take the place of the
served ones, so that the verdict itself is shown to reject it.

A second number, ``short_outputs``, counts sampled requests that did not
emit exactly the output length they asked for (the mix turns end-of-
sequence off); its limit is 0.
"""
from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.onchip import spec as spec_mod
from benchmarks.onchip import traffic as traffic_mod


def sample(finished, k, seed):
    """k finished requests drawn from the seed, with the longest in it."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i][0]) + finished[i][2]))
    rest = order[1:]
    rng = traffic_mod.seed_rng(seed + 2)
    pick = list(rng.choice(rest, size=min(k - 1, len(rest)), replace=False)) \
        if rest else []
    return [finished[i] for i in [order[0]] + pick]


def _gaps(ref, served):
    """Per-row gap of ``served`` below the best logit of ``ref``."""
    best = ref.max(axis=-1)
    got = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    return best - got


@functools.partial(jax.jit, static_argnames=("fn", "quant"))
def _readings(params, tokens, rows, served, *, fn, quant):
    ref = fn(params, tokens, rows)
    out = {"program": _gaps(ref, served)}
    for q in quant:
        low = fn(params, tokens, rows, quant=q)
        out[q] = _gaps(ref, jnp.argmax(low, axis=-1).astype(jnp.int32))
    return out


def readings(cell, params, picked, controls=()):
    """-> ({"program" | control: widest gap}, tokens compared)."""
    config = cell.config
    sizes = config["sizes"]
    ref_mod = spec_mod.reference(config["name"])
    fn = functools.partial(ref_mod.logits, sizes=sizes)
    seq_len = int(config["serve"]["max_len"])
    n_rows = int(cell.traffic["output"]["max"])
    widest = {k: 0.0 for k in ("program",) + tuple(controls)}
    n_tok = 0
    with jax.default_matmul_precision("highest"):
        for prompt, outputs, _n in picked:
            p = len(prompt)
            ctx = np.concatenate([prompt, np.asarray(outputs[:-1],
                                                     np.int32)])
            tokens = np.zeros(seq_len, np.int32)
            tokens[:len(ctx)] = ctx
            rows = np.minimum(p - 1 + np.arange(n_rows), seq_len - 1)
            served = np.zeros(n_rows, np.int32)
            served[:len(outputs)] = outputs
            got = _readings(params, tokens, rows.astype(np.int32), served,
                            fn=fn, quant=tuple(controls))
            m = len(outputs)
            for k, v in got.items():
                widest[k] = max(widest[k], float(np.asarray(v)[:m].max()))
            n_tok += m
    return widest, n_tok


def check(cell, params, finished, seed, controls=(), control_as_program=""):
    """-> (checks {name: {"value", "limit"}}, control readings).

    ``control_as_program`` names a control whose first tokens are judged
    in place of the served ones: the gap compared is then the gap of the
    tokens that control puts first."""
    limits = cell.cell["limits"]
    k = int(cell.traffic["check_requests"])
    picked = sample(finished, k, seed)
    short = sum(len(o) != n for _p, o, n in picked)
    if control_as_program and control_as_program not in controls:
        controls = tuple(controls) + (control_as_program,)
    widest, n_tok = readings(cell, params, picked, controls)
    if control_as_program:
        widest["program"] = widest[control_as_program]
        print(f"check: the {control_as_program} control's tokens stand in "
              "for the served ones", file=sys.stderr)
    if not picked:
        widest["program"] = float("inf")
    print(f"check: {len(picked)} of {len(finished)} finished requests, "
          f"{n_tok} served tokens against the float32 reference"
          + "".join(f"; {q} control widest gap {widest[q]!r}"
                    for q in controls), file=sys.stderr)
    checks = {
        "max_logit_gap": {"value": widest["program"],
                          "limit": limits["max_logit_gap"]},
        "short_outputs": {"value": short, "limit": 0},
    }
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return checks, {q: widest[q] for q in controls}
