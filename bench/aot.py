"""Compile a cell's two step programs for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python3 bench/aot.py --workload qwen3-4b.long_prefill

Builds the serving engine at the cell's configuration and geometry (the
same ``max_nb``, prefill rows, decode rows and pool blocks as ``run.py``),
lowers the chunk-prefill and decode step programs on shapes placed on one
chip of a described ``v5e:2x2``, compiles them with the TPU compiler, and
prints ``memory_analysis()`` of each and the names of their Pallas custom
calls.  A compile that passes is not a chip run: it gives sizes, never
times.  ``--decode-rows`` / ``--max-prefill-tokens`` try another geometry;
``--config`` / ``--traffic`` compile a pair that is not a cell yet, and
``--mesh data=1,model=4`` compiles the sharded step programs over the
described 2x2 (``memory_analysis`` is then per device).
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default=None)
    ap.add_argument("--config", default=None)
    ap.add_argument("--traffic", default=None)
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--decode-rows", type=int, default=None)
    ap.add_argument("--max-prefill-tokens", type=int, default=None)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import serve
    import spec as spec_mod

    jax.config.update("jax_enable_compilation_cache", False)
    if args.workload:
        cell = spec_mod.load_cell(args.workload)
    else:
        cell = spec_mod.Cell(
            name=f"{args.config}.{args.traffic}",
            config=spec_mod.load_json(HERE / "configs" / f"{args.config}.json"),
            traffic=spec_mod.load_json(HERE / "traffic"
                                       / f"{args.traffic}.json"),
            limits={}, chips=1, end_to_end=[], per_layer=[])
    tr = dict(cell.traffic)
    if args.decode_rows:
        tr["decode_rows"] = args.decode_rows
    if args.max_prefill_tokens:
        tr["max_prefill_tokens"] = args.max_prefill_tokens
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    cfg = serve.model_config(cell.config)
    from repro.models.model import build_model
    from repro.serving.engine import Engine
    model = build_model(cfg)
    pshape = jax.eval_shape(lambda k: model.init(k, dtype=cfg.compute_dtype),
                            jax.random.PRNGKey(0))
    geo = serve.geometry(cell.config, tr)
    eng = Engine(model, pshape, method=cell.config["program"]["method"],
                 backend="pallas")
    mesh = None
    if args.mesh:
        import numpy as np
        from jax.sharding import AxisType, Mesh

        from repro.launch.mesh import parse_mesh_spec
        from repro.sharding import ctx as shctx
        from repro.sharding import specs as sh
        axes = parse_mesh_spec(args.mesh)
        mesh = Mesh(np.array(topo.devices).reshape(tuple(axes.values())),
                    tuple(axes), axis_types=(AxisType.Auto,) * len(axes))
        eng.mesh = mesh
        eng._param_sh = sh.to_shardings(
            mesh, sh.param_specs(cfg, pshape, mesh))
        shctx.set_policy(mesh, tuple(a for a in ("pod", "data")
                                     if a in mesh.axis_names))
    fns = eng._continuous_fns(geo["block_size"], geo["max_nb"],
                              geo["prefill_rows"], geo["decode_rows"],
                              geo["num_blocks"])
    data = jax.eval_shape(lambda: model.init_cache(geo["num_blocks"],
                                                   geo["block_size"]))

    def on_chip(tree):
        if mesh is not None:
            return tree          # the step programs carry in_shardings
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    def sds(shape, dtype=jnp.int32):
        if mesh is not None:
            return jax.ShapeDtypeStruct(shape, dtype)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    key = sds((2,), jnp.uint32)
    bp, bd, nb = geo["prefill_rows"], geo["decode_rows"], geo["max_nb"]
    chunk = cfg.quoka.chunk_size
    args_of = {
        "prefill_step": (on_chip(pshape), on_chip(data), sds((bp, nb)),
                         sds((bp, chunk)), sds((bp,)), sds((bp,)), key),
        "decode_step": (on_chip(pshape), on_chip(data), sds((bd, nb)),
                        sds((bd,)), sds((bd,)), sds((bd,)), key),
    }
    out = {"workload": cell.name, "geometry": geo}
    import contextlib
    for fn, (name, a) in zip(fns, args_of.items()):
        with (mesh if mesh is not None else contextlib.nullcontext()):
            compiled = fn.lower(*a).compile()
        ma = compiled.memory_analysis()
        text = compiled.as_text()
        kernels = sorted(set(re.findall(
            r"custom_call_target=\"(tpu_custom_call)\"", text)))
        calls = sorted(set(re.findall(r"(\S+) = \S+ custom-call\(", text)))
        out[name] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "generated_code_bytes": ma.generated_code_size_in_bytes,
            "total_bytes": (ma.argument_size_in_bytes
                            + ma.output_size_in_bytes
                            + ma.temp_size_in_bytes
                            - ma.alias_size_in_bytes),
            "custom_call_targets": kernels,
            "custom_calls": calls[:20],
        }
        print(json.dumps({name: out[name]}), flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
