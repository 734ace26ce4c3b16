#!/usr/bin/env python3
"""How many KV pages a configuration's arena can hold on one chip.

    JAX_PLATFORMS=cpu python3 chipbench/sizing.py <config-name> [hbm_gib]

Compiles the configuration's step programs (decode, fused chunk + decode,
chunk prefill) for a described TPU v5e, at two arena sizes, and reads
``compiled.memory_analysis()``: arguments, outputs and temporaries. The
temporaries are fitted as ``a + b * pages`` (a step that copies the arena
shows ``b`` near one page), and the arena is sized so that weights, arena
and the largest step's temporaries fit ``hbm_gib`` (default 15.0, under the
chip's 16 GB, leaving room for the sampler and the runtime). No chip needed.
"""
from __future__ import annotations

import functools
import os
import sys
from pathlib import Path
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def analyse(config: dict, pages: int) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import repro.kernels.decode_attention.ops as ops
    from chipbench.run import model_config
    from repro.models.api import build_model
    from repro.models.pdefs import abstract_from_defs

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    e = config["engine"]
    cfg, _ = model_config(config)
    model = build_model(cfg, max_seq=e["max_seq"])

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    P = sds(model.abstract_params())
    C = sds(abstract_from_defs(model.paged_cache_defs(pages + 1,
                                                      e["page_size"])))

    def s(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    B, n = e["max_batch"], e["max_seq"] // e["page_size"]
    chunk = e["prefill_chunk"]
    out = {}
    with mock.patch.object(ops, "_on_tpu", lambda: True):
        ps = e["page_size"]
        progs = {
            "decode": (functools.partial(model.decode_step_paged,
                                         page_size=ps),
                       (P, C, s((B, 1)), s((B,)), s((B, n)))),
            "fused": (functools.partial(model.fused_step, page_size=ps),
                      (P, C, s((B, 1)), s((B,)), s((B, n)), s((1, chunk)),
                       s(()), s(()), s((n,)))),
            "prefill": (functools.partial(model.prefill_paged, page_size=ps),
                        (P, C, s((1, chunk)), s(()), s(()), s((n,)))),
        }
        for name, (fn, args) in progs.items():
            c = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
            m = c.memory_analysis()
            out[name] = {"temp": m.temp_size_in_bytes,
                         "args": m.argument_size_in_bytes,
                         "out": m.output_size_in_bytes}
    return out


def main() -> int:
    import json
    from chipbench.run import arch
    name = sys.argv[1]
    hbm = float(sys.argv[2]) if len(sys.argv) > 2 else 15.0
    config = json.load(open(ROOT / "chipbench" / "configs" / f"{name}.json"))
    model_arch = arch(config)
    dims = model_arch.dims(config["model"])
    page = (model_arch.kv_bytes_per_token(dims)
            * config["engine"]["page_size"])
    p1, p2 = 1024, 4096
    a1, a2 = analyse(config, p1), analyse(config, p2)
    weights = a1["decode"]["args"] - (p1 + 1) * page
    fit = {}
    for k in a1:
        b = (a2[k]["temp"] - a1[k]["temp"]) / (p2 - p1)
        fit[k] = (a1[k]["temp"] - b * p1, b)
    # weights + arena (pages + trash) + temporaries(pages) <= hbm
    budget = hbm * 2 ** 30 - weights
    pages = min(int((budget - a - page) // (page + b)) for a, b in
                fit.values())
    print(json.dumps({"config": name, "weights_bytes": weights,
                      "page_bytes": page, "temp_fit": fit,
                      "at_1024": a1, "at_4096": a2, "hbm_gib": hbm,
                      "num_pages": pages}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
