#!/usr/bin/env python3
"""Compile the cells' programs at real size for a DESCRIBED ``v5e:2x2``
(no chip attached) and print ``memory_analysis``, so depth and pool are
settled before chip time is spent.

    JAX_PLATFORMS=cpu python3 chipbench/compile_rehearsal.py [--train-depths 8,6,4]

- the serving configuration's decode token-step (``decode_step_paged`` with
  the paged-attention kernel, batch ``max_batch_size``, the widest block
  table) and one prefill chunk (``prefill_chunk_paged``, 256 tokens, the
  widest table), with the pool the configuration file states;
- the training configuration's ``make_train_step`` step at each depth.

A compile that passes is not a chip run: it says what the chip's compiler
accepts and how many bytes ONE program holds, not what else the process
keeps on the device, and nothing about time.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def gb(n) -> str:
    return f"{n / 1e9:.2f} GB"


def report(name, compiled, t0):
    """The compiler accepted the program for the chip's 15.75 GiB; these are
    its own byte counts (temporaries include the donated outputs)."""
    m = compiled.memory_analysis()
    print(f"{name}: ACCEPTED, compiled in {time.monotonic() - t0:.0f}s; "
          f"arguments {gb(m.argument_size_in_bytes)}, outputs "
          f"{gb(m.output_size_in_bytes)}, aliased {gb(m.alias_size_in_bytes)}"
          f", temporaries {gb(m.temp_size_in_bytes)}; kernel in the program: "
          f"{'tpu_custom_call' in compiled.as_text()}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--serve-config", default="mistral7b-v03-d16")
    ap.add_argument("--train-config", default="mistral7b-v03-train")
    ap.add_argument("--train-depths", default="8,6,4")
    ap.add_argument("--skip-serve", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import spec
    from ray_tpu.models import llama
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.parallel import make_train_step

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def abstract(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)

    def model_config(c, depth, seq):
        return LlamaConfig(
            vocab_size=c["vocab_size"], dim=c["hidden_size"], n_layers=depth,
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], ffn_dim=c["intermediate_size"],
            max_seq_len=seq, rope_theta=c["rope_theta"],
            rms_norm_eps=c["rms_norm_eps"],
            tie_embeddings=c["tie_word_embeddings"], param_dtype=jnp.bfloat16)

    if not args.skip_serve:
        c = spec.load_json(os.path.join(
            ROOT, "chipbench", "configs", args.serve_config + ".json"))
        e = c["engine"]
        cfg = model_config(c, c["num_hidden_layers"], e["max_seq_len"])
        params = abstract(jax.eval_shape(
            lambda: llama.init_params(cfg, jax.random.PRNGKey(0))))
        pool = abstract(jax.eval_shape(lambda: llama.init_paged_kv_cache(
            cfg, e["num_blocks"], e["block_size"])))
        w = e["max_seq_len"] // e["block_size"]
        b = e["max_batch_size"]
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)  # noqa: E731
        print(f"{args.serve_config}: weights "
              f"{gb(sum(x.size * 2 for x in jax.tree.leaves(params)))}, pool "
              f"{gb(sum(x.size * 2 for x in jax.tree.leaves(pool)))} "
              f"({e['num_blocks']} blocks)", flush=True)
        t0 = time.monotonic()
        decode = jax.jit(
            lambda p, t, pl, tb, ln: llama.decode_step_paged(
                cfg, p, t, pl, tb, ln, use_kernel=True),
            donate_argnums=2).lower(params, i32(b), pool, i32(b, w), i32(b)
                                    ).compile()
        report(f"decode token-step, batch {b}, table {w} blocks", decode, t0)
        t0 = time.monotonic()
        prefill = jax.jit(
            lambda p, t, pl, tb, p0: llama.prefill_chunk_paged(
                cfg, p, t, pl, tb, p0),
            donate_argnums=2).lower(params, i32(1, 256), pool, i32(1, w + 16),
                                    i32()).compile()
        report(f"prefill chunk, 256 tokens, table {w + 16} blocks", prefill, t0)

    c = spec.load_json(os.path.join(
        ROOT, "chipbench", "configs", args.train_config + ".json"))
    t = c["trainer"]
    for depth in (int(d) for d in args.train_depths.split(",") if d):
        cfg = model_config(c, depth, t["seq_len"])
        opt = optax.adamw(t["learning_rate"], b1=t["b1"], b2=t["b2"],
                          weight_decay=t["weight_decay"], mu_dtype=jnp.bfloat16)
        init_fn, step_fn = make_train_step(cfg, None, optimizer=opt)
        state = abstract(jax.eval_shape(init_fn, jax.random.PRNGKey(0)))
        tokens = jax.ShapeDtypeStruct((t["batch"], t["seq_len"]), jnp.int32,
                                      sharding=chip)
        t0 = time.monotonic()
        # the program picks the flash kernel where jax.default_backend() is
        # "tpu"; here it is the CPU, so this SCRIPT steers it (no option of
        # the program does) while the step is traced
        real_backend = jax.default_backend
        jax.default_backend = lambda: "tpu"
        try:
            lowered = step_fn.lower(state, tokens)
        finally:
            jax.default_backend = real_backend
        try:
            compiled = lowered.compile()
        except Exception as ex:  # noqa: BLE001 - the compiler's refusal is the answer
            print(f"train step, depth {depth} ({cfg.num_params / 1e9:.3f} B): "
                  f"REFUSED after {time.monotonic() - t0:.0f}s: "
                  f"{str(ex)[:400]}", flush=True)
            continue
        report(f"train step, depth {depth} ({cfg.num_params / 1e9:.3f} B), "
               f"batch {t['batch']} x {t['seq_len']}", compiled, t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
