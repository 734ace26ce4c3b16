"""The control at a size a test run holds: the float32 reference computed
with int8 weights, put in the program's place, must come out not correct
by the harness's own verdict (``check.readings`` and ``check.judge``),
where the program comes out correct.

On the chip the control runs at each cell's own size against the
configuration's limits (``chipbench/control.py``; readings and limits in
PERF.md). Here the program serves a fixed batch through the scheduler at
toy widths with logits of the same spread (~3) as the cells', and the
limits are set between this size's own readings."""
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import check
from chipbench.run import arch, build_engine, model_config
from chipbench.tests._tiny import tiny_cell

# toy widths: 4 layers of d 128, vocab 8,192; logits' spread ~3 as in the
# cells (embed_std * sqrt(d) = 3)
MODEL = dict(num_hidden_layers=4, hidden_size=128, num_attention_heads=4,
             num_key_value_heads=2, intermediate_size=384, vocab_size=8192)


TOY_LIMITS = {"worst_path_mean_gap": 0.012, "max_logit_gap": 0.4}


@pytest.mark.parametrize("seed", [11, 12])
def test_control_reads_worse_than_the_program(seed):
    from repro.serving.engine import Request
    from repro.serving.scheduler import TierScheduler
    config = tiny_cell()["config"]
    config["model"].update(MODEL)
    config["init"]["embed_std"] = 3.0 / np.sqrt(128)
    cfg, dims = model_config(config)
    model_arch = arch(config)
    eng = build_engine(config, cfg, model_arch.program_params(
        seed, dims, config["init"]))
    sched = TierScheduler({"serve": eng})
    rng = np.random.default_rng(seed)
    ctx = rng.integers(259, dims["vocab"], 96).tolist()
    reqs = [Request(prompt="", max_new_tokens=16, prompt_ids=ctx + rng
                    .integers(259, dims["vocab"], 6).tolist())
            for _ in range(8)]
    for r in reqs:
        sched.submit(r, "serve")
    done = sched.drain()
    picks = [SimpleNamespace(prompt=c.request.prompt_ids,
                             tokens=c.token_ids) for c in done]
    served, ctrl = check.control_gaps(model_arch, seed, config["model"],
                                      config["init"], picks)
    sound, control = check.readings(served, picks), check.readings(ctrl,
                                                                  picks)
    assert sound["served_tokens"] == control["served_tokens"] >= 100
    # the program reads 0.00087 and 0.0054 (mean), 0.066 and 0.171 (max);
    # the control 0.0191 and 0.0498, 0.646 and 0.928 (CPU, these seeds):
    # toy limits between them
    assert check.judge(sound, TOY_LIMITS)[0] is True
    ok, compared = check.judge(control, TOY_LIMITS)
    assert ok is False
    assert all(compared[k]["value"] > compared[k]["limit"]
               for k in TOY_LIMITS)
