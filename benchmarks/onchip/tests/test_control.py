"""The control: the float32 reference computed one precision step below
the configuration's bfloat16 (float8 e4m3, ``references/common.py``) and
put in the program's place must come out as not correct, while the
program's own served tokens pass, under the cell's limit; the verdict
(``check.check`` with ``control_as_program``) says so too.

At the cell's own size this runs on the chip (``calibrate.py``); here it
runs at a size a CPU test can hold: the same architectures at 8 layers,
d_model 256 and a vocabulary of 8192, serving six fixed requests to
completion through the engine."""
import copy

import numpy as np
import pytest

from benchmarks.onchip import check, driver, spec, weights

MID = {"n_layers": 8, "d_model": 256, "n_heads": 4, "n_kv_heads": 2,
       "head_dim": 64, "d_ff": 768, "vocab": 8192}


def _mid_cell(name):
    cell = spec.load_cell(name)
    cfg = copy.deepcopy(cell.config)
    keep = {k: v for k, v in MID.items() if k in cfg["sizes"]}
    cfg["sizes"].update(keep)
    cfg["model"].update(keep)
    cfg["serve"].update(slots=8, max_len=256, prefill_chunk=64)
    if cfg["serve"].get("kv_layout") == "paged":
        cfg["serve"]["num_pages"] = 128
    cell.config = cfg
    cell.traffic = {**cell.traffic, "output": {"max": 64}}
    return cell


def _serve(cell, seed):
    model = driver.build_model(cell.config)
    params = weights.make(model, seed)
    eng = driver.build_engine(model, params, cell.config)
    rng = np.random.default_rng(seed)
    reqs = []
    for plen, n in zip((64, 96, 128, 150, 170, 192), (64, 48, 40, 32, 24, 16)):
        prompt = rng.integers(0, MID["vocab"], plen, dtype=np.int32)
        reqs.append((prompt, eng.submit(prompt, max_new_tokens=n), n))
    eng.run()
    served = [(p, [int(t) for t in r.outputs], n) for p, r, n in reqs]
    return params, served


@pytest.mark.parametrize("name", ["smollm360m.chat", "mingru360m.longdoc"])
@pytest.mark.parametrize("seed", [1, 2])
def test_fp8_control_fails_where_the_program_passes(name, seed):
    cell = _mid_cell(name)
    params, served = _serve(cell, seed)
    widest, n_tok = check.readings(cell, params, served, controls=("fp8",))
    limit = cell.cell["limits"]["max_logit_gap"]
    assert n_tok == sum(n for _p, _o, n in served)
    assert widest["program"] <= limit < widest["fp8"], widest
    # the verdict itself, with the control's tokens in the served ones' place
    cell.traffic = {**cell.traffic, "check_requests": len(served)}
    checks, _ = check.check(cell, params, served, seed,
                            control_as_program="fp8")
    assert checks["max_logit_gap"]["value"] == widest["fp8"]
    assert checks["max_logit_gap"]["value"] > limit

