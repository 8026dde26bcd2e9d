#!/usr/bin/env python3
"""Compile a cell's served programs at its real size for a described TPU
v5e, with no chip attached, and print each program's memory analysis.

    JAX_PLATFORMS=cpu python3 benchmarks/onchip/rehearse_compile.py \\
        --workload <cell> [--stream 2048 ...]

For the largest packed-prefill stream the cell can produce (or each
``--stream`` given) and the widest decode batch, it lowers the engine's
own ``packed_paged`` / ``decode_paged`` bodies with the cell's weights and
page pool as shapes only (nothing is allocated at full size), compiles
them for one chip of a ``v5e:2x2`` topology and prints arguments,
outputs, temporaries and aliased bytes. ``jax.default_backend`` reads
"tpu" while it runs, so the engine takes the kernel routes a chip takes.
A compile that passes here is not a chip run.
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--stream", type=int, action="append", default=[])
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import run as harness
    import spec
    from repro.models.transformer import init_params

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(args.workload)
    m, mix = dict(cell.config), harness.merged_mix(cell.traffic, False)
    serving = mix["serving"]
    adapter = cell.adapter()
    cfg = adapter.program_config(m)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        small = dict(serving, n_pages=1)
        engine = harness.build_engine(cfg, None, small, 1)
        nb, n_pages = serving["max_batch"], serving["n_pages"]

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

        params = jax.tree.map(lambda s: sds(s.shape, s.dtype),
                              jax.eval_shape(lambda: init_params(
                                  jax.random.PRNGKey(0), cfg)))
        cache = [{k: sds((v.shape[0], n_pages) + v.shape[2:], v.dtype)
                  for k, v in c.items()} for c in engine.cache]
        width = n_pages              # block tables span the whole pool
        streams, _ = harness.warm_shapes(engine, serving)
        i32, f32 = jnp.int32, jnp.float32
        pp = (sds((nb,), f32), sds((nb,), i32), sds((nb,), f32),
              sds((nb,), i32), sds((nb,), i32))
        for T in args.stream or [streams[-1]]:
            fn = jax.jit(engine._prefill_packed_paged_impl,
                         donate_argnums=(6,), static_argnums=(13,))
            lowered = fn.lower(
                params, sds((T,), i32), sds((nb,), i32), sds((nb,), i32),
                sds((nb,), i32), sds((nb,), i32), cache,
                [sds((nb, width), i32)], *pp, True)
            report(f"packed_paged T={T}", lowered.compile())
        dp = tuple(sds((nb,), d) for d in (f32, i32, f32, i32, i32))
        fn = jax.jit(engine._decode_paged_impl, donate_argnums=(2,),
                     static_argnums=(10,))
        lowered = fn.lower(params, sds((nb, 1), i32), cache,
                           sds((nb,), i32), [sds((nb, width), i32)], *dp,
                           True)
        report(f"decode_paged B={nb}", lowered.compile())
    finally:
        jax.default_backend = real_backend
    return 0


def report(name: str, compiled) -> None:
    ma = compiled.memory_analysis()
    kernels = compiled.as_text().count("tpu_custom_call")
    print(f"{name}: arguments {ma.argument_size_in_bytes} outputs "
          f"{ma.output_size_in_bytes} temporaries {ma.temp_size_in_bytes} "
          f"aliased {ma.alias_size_in_bytes} generated code "
          f"{ma.generated_code_size_in_bytes} bytes; Pallas kernel calls "
          f"in the program text: {kernels}", flush=True)


if __name__ == "__main__":
    sys.exit(main())
