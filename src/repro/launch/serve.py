"""Batched serving driver: prefill a request batch, then decode N tokens
through the pipelined ``serve_step`` (greedy).

    PYTHONPATH=src python -m repro.launch.serve --arch codeqwen1.5-7b \
        --prompt-len 64 --decode-steps 16 --batch 8
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache


def run(args) -> dict:
    from repro.configs import get_arch, ShapeConfig
    from repro.data.tokens import token_batch, frontend_embeds
    from repro.models.transformer.model import (
        Topology, init_params, make_prefill_step, make_serve_step,
    )

    cfg = get_arch(args.arch, smoke=not args.full_arch)
    n_dev = jax.device_count()
    stages = args.stages if args.stages > 1 else 1
    data = max(n_dev // stages, 1)
    mesh = jax.make_mesh((data, stages), ("data", "model"))
    topo = Topology(num_stages=stages, fsdp_size=data, num_micro=args.chunks)

    total = args.prompt_len + args.decode_steps
    pshape = ShapeConfig("serve_prefill", args.prompt_len, args.batch, "prefill")
    dshape = ShapeConfig("serve_decode", total + 16, args.batch, "decode")

    part = make_prefill_step(cfg, topo, pshape, mesh, dtype=jnp.float32)
    sart = make_serve_step(cfg, topo, dshape, mesh, dtype=jnp.float32)

    params = init_params(cfg, jax.random.PRNGKey(args.seed), num_stages=stages, dtype=jnp.float32)
    params = jax.device_put(params, part.in_shardings[0])

    s_front = int(args.prompt_len * cfg.frontend_frac) if cfg.frontend != "none" else 0
    prompt = {
        "tokens": jnp.asarray(token_batch(
            batch=args.batch, seq=args.prompt_len - s_front, vocab=cfg.vocab_size, seed=args.seed,
        ))[:, :-1][:, : args.prompt_len - s_front]
    }
    if s_front:
        prompt["frontend_embeds"] = jnp.asarray(frontend_embeds(
            batch=args.batch, seq=s_front, d_model=cfg.d_model, seed=args.seed,
        ))

    # prefill into a decode-width cache: run prefill at prompt length, then
    # copy entries into the wider serving cache (host-side splice)
    pcache0 = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), part.abstract_inputs[1])
    t0 = time.perf_counter()
    logits, pcache = jax.jit(part.fn, in_shardings=part.in_shardings,
                             out_shardings=part.out_shardings)(params, pcache0, prompt)
    t_prefill = time.perf_counter() - t0

    dcache = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), sart.abstract_inputs[1])

    def splice(dst, src):
        if dst.ndim >= 5 and src.ndim == dst.ndim and src.shape[:3] == dst.shape[:3]:
            # KV-like leaves: (S, NM, per, B, W, ...) — copy prefilled W slots
            w = src.shape[4]
            return dst.at[:, :, :, :, :w].set(src.astype(dst.dtype))
        return src.astype(dst.dtype)  # state-like leaves (ssm/conv): carry over

    dcache = jax.tree_util.tree_map(splice, dcache, pcache)

    step = jax.jit(sart.fn, in_shardings=sart.in_shardings, out_shardings=sart.out_shardings)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    generated = [np.asarray(tok)]
    t0 = time.perf_counter()
    for i in range(args.decode_steps):
        pos = jnp.asarray(args.prompt_len + i, jnp.int32)
        tok, dcache = step(params, dcache, {"tokens": tok, "pos": pos})
        generated.append(np.asarray(tok))
    jax.block_until_ready(tok)
    t_decode = time.perf_counter() - t0

    gen = np.stack(generated, axis=1)
    out = {
        "arch": cfg.name,
        "batch": args.batch,
        "prefill_s": round(t_prefill, 3),
        "decode_s_per_tok": round(t_decode / max(args.decode_steps, 1), 4),
        "tokens_generated": int(gen.size),
        "sample": gen[0][:8].tolist(),
    }
    print(out)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="codeqwen1.5-7b")
    ap.add_argument("--full-arch", action="store_true")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--decode-steps", type=int, default=16)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--stages", type=int, default=1)
    ap.add_argument("--chunks", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    run(args)


if __name__ == "__main__":
    main()
