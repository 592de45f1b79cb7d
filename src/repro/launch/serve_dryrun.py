import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Enterprise XMR serving dry-run: the paper's own deployment (§6) on the
production mesh.

Lowers + compiles the sharded beam-search serving step for the 100M-label,
d=4M semantic product-search model (tree [64,32,32,32,48] -> 100.7M leaves)
with ShapeDtypeStruct weights — proving the paper's enterprise model fits
and runs on a v5e pod, and reporting its roofline terms. This model does NOT
fit one host (leaf chunk tiles ≈ 309 GB f32); the 16-way label-sharded
layout is the point.

    PYTHONPATH=src python -m repro.launch.serve_dryrun [--batch 1024]
"""

import argparse
import functools
import json
import time
from typing import List

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import mscm as mscm_lib
from repro.core.beam import NEG_INF, beam_step
from repro.launch import hw
from repro.launch.hlo_stats import collective_stats
from repro.launch.mesh import make_production_mesh

# enterprise tree geometry (paper §6: L = 100M, d = 4M, branching 32-ish)
D_FEAT = 4_000_000
BRANCHING = [64, 32, 32, 32, 48]          # level sizes 64 ... 100,663,296
LEVEL_NNZ = 64                             # pruned ranker nnz per column
ELL_R = 768                                # chunk union rows (64 nnz x B overlap)
QUERY_NNZ = 256


def level_sizes() -> List[int]:
    out, n = [], 1
    for b in BRANCHING:
        n *= b
        out.append(n)
    return out


def serve_step_spec(batch: int, beam: int, topk: int, mesh):
    sizes = level_sizes()
    n_levels = len(sizes)
    # abstract weights: chunked ELL per level (bf16 values for serving)
    layer_specs = []
    layer_shardings = []
    for li, size in enumerate(sizes):
        b = BRANCHING[li]
        c = sizes[li - 1] if li else 1
        r = min(ELL_R, ((LEVEL_NNZ * b + 127) // 128) * 128) if li == 0 else ELL_R
        rows = jax.ShapeDtypeStruct((c, r), jnp.int32)
        vals = jax.ShapeDtypeStruct((c, b, r), jnp.bfloat16)
        is_leaf = li == n_levels - 1
        spec_rows = P("model", None) if is_leaf else P()
        spec_vals = P("model", None, None) if is_leaf else P()
        layer_specs.append((rows, vals))
        layer_shardings.append(
            (NamedSharding(mesh, spec_rows), NamedSharding(mesh, spec_vals))
        )
    xi = jax.ShapeDtypeStruct((batch, QUERY_NNZ), jnp.int32)
    xv = jax.ShapeDtypeStruct((batch, QUERY_NNZ), jnp.float32)
    q_shard = NamedSharding(mesh, P("data", None))

    flat_specs = [a for pair in layer_specs for a in pair]
    flat_shards = [a for pair in layer_shardings for a in pair]

    def serve(xi, xv, *layers):
        pairs = [(layers[2 * i], layers[2 * i + 1]) for i in range(n_levels)]
        upper, (leaf_rows, leaf_vals) = pairs[:-1], pairs[-1]

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P("data", None), P("data", None),
                      tuple(P() for _ in range(2 * (n_levels - 1))),
                      P("model", None), P("model", None, None)),
            out_specs=(P("data", None), P("data", None)),
            check_vma=False,
        )
        def run(xi, xv, upper_flat, leaf_rows, leaf_vals):
            n = xi.shape[0]
            xd = mscm_lib.scatter_dense(xi, xv, D_FEAT)
            parent = jnp.zeros((n, 1), jnp.int32)
            scores = jnp.ones((n, 1), jnp.float32)
            for li in range(n_levels - 1):
                rows_l, vals_l = upper_flat[2 * li], upper_flat[2 * li + 1]
                bc = parent.shape[1]
                bq = jnp.repeat(jnp.arange(n, dtype=jnp.int32), bc)
                logits = mscm_lib.mscm_dense_lookup(
                    xd, rows_l, vals_l.astype(jnp.float32), bq, parent.reshape(-1)
                ).reshape(n, bc, BRANCHING[li])
                nb = min(beam, sizes[li])
                parent, scores = beam_step(parent, scores, logits, sizes[li], nb)
            my = jax.lax.axis_index("model")
            c_local = leaf_vals.shape[0]
            bc = parent.shape[1]
            bq = jnp.repeat(jnp.arange(n, dtype=jnp.int32), bc)
            fp = parent.reshape(-1)
            local_c = jnp.clip(fp - my * c_local, 0, c_local - 1)
            logits = mscm_lib.mscm_dense_lookup(
                xd, leaf_rows, leaf_vals.astype(jnp.float32), bq, local_c
            ).reshape(n, bc, BRANCHING[-1])
            mine = ((fp // c_local) == my).reshape(n, bc, 1)
            child = fp.reshape(n, bc, 1) * BRANCHING[-1] + jnp.arange(BRANCHING[-1])
            comb = jnp.where(mine, jax.nn.sigmoid(logits) * scores[..., None], NEG_INF)
            ls, pos = jax.lax.top_k(comb.reshape(n, -1), topk)
            li_ = jnp.take_along_axis(child.reshape(n, -1), pos, axis=1)
            als = jax.lax.all_gather(ls, "model", axis=1).reshape(n, -1)
            ali = jax.lax.all_gather(li_, "model", axis=1).reshape(n, -1)
            gs, gp = jax.lax.top_k(als, topk)
            return gs, jnp.take_along_axis(ali, gp, axis=1).astype(jnp.int32)

        return run(xi, xv, tuple(layers[: 2 * (n_levels - 1)]), leaf_rows, leaf_vals)

    return serve, (xi, xv, *flat_specs), (q_shard, q_shard, *flat_shards)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=1024)
    ap.add_argument("--beam", type=int, default=10)
    ap.add_argument("--topk", type=int, default=10)
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    chips = mesh.devices.size
    fn, specs, shardings = serve_step_spec(args.batch, args.beam, args.topk, mesh)
    t0 = time.time()
    with jax.sharding.set_mesh(mesh):
        compiled = jax.jit(fn, in_shardings=shardings).lower(*specs).compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        coll = collective_stats(compiled.as_text())
    flops = float(cost.get("flops", 0)) * chips
    byts = float(cost.get("bytes accessed", 0)) * chips
    cb = coll.get("TOTAL", {}).get("operand_bytes", 0.0) * chips
    terms = hw.roofline_terms(flops=flops, bytes_hbm=byts, bytes_collective=cb,
                              chips=chips)
    sizes = level_sizes()
    rec = {
        "model": f"enterprise L={sizes[-1]:,} d={D_FEAT:,} tree={BRANCHING}",
        "batch": args.batch, "beam": args.beam, "chips": chips,
        "compile_s": round(time.time() - t0, 1),
        "memory": {
            "argument_gb_per_device": mem.argument_size_in_bytes / 1e9,
            "temp_gb_per_device": mem.temp_size_in_bytes / 1e9,
        },
        "roofline": terms,
        "per_query_bound_us": 1e6 * terms["bound_s"] / args.batch,
        "collectives": {k: v for k, v in coll.items()},
    }
    print(json.dumps(rec, indent=1))
    out = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun",
                       f"enterprise__serve__{'multi' if args.multi_pod else 'single'}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)


if __name__ == "__main__":
    main()
