"""Pipeline parallelism: layer stages over the `pipeline` mesh axis.

The reference surfaces PP as a first-class degree it schedules placement
for but delegates the schedule itself to the engine (reference:
llm/_internal/serve/deployments/llm/vllm/vllm_models.py:181-191 folds
`pipeline_parallel_degree` into the placement-group size).  A TPU-native
rebuild runs the schedule itself, the SPMD way:

  - the stacked layer params [L, ...] shard their leading dim over the
    `pipeline` axis — stage p owns layers [p*L/pp, (p+1)*L/pp); no host-side
    param surgery, just a PartitionSpec change
  - the microbatch schedule is ONE compiled program: a `shard_map` over the
    `pipeline` axis scans M + pp - 1 ticks; each tick every stage applies
    its layer block and hands its activation to the next stage with
    `lax.ppermute` (p2p, DCN-tolerant — the axis is outermost in MESH_AXES)
  - the BACKWARD schedule comes from autodiff: scan + ppermute are
    differentiable (ppermute transposes to the reversed permutation), so
    `jax.grad` of the pipelined loss IS the reversed-pipeline backward —
    no hand-written 1F1B state machine to get wrong
  - per-tick stage compute is wrapped in `jax.checkpoint`, so activations
    between ticks (not within stage blocks) are all that live across the
    forward — GPipe-style memory behaviour

Embedding / final-norm / lm-head are replicated over the pipeline axis and
applied under a first/last-stage mask; their logit computation runs on every
stage and is masked (pp× head-FLOPs overhead — acceptable at pp ≤ 4; a
lax.cond guard is the known optimization if profiles demand it).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.models import llama
from ray_tpu.ops.norms import rms_norm


def pipeline_param_specs(cfg) -> dict:
    """llama param_specs with the stacked-layer dim sharded by stage."""
    specs = llama.param_specs(cfg)
    specs["layers"] = jax.tree.map(
        lambda s: P(*(("pipeline",) + tuple(s)[1:])), specs["layers"],
        is_leaf=lambda x: isinstance(x, P))
    return specs


def _ce_loss(cfg, logits, tokens):
    """Mean next-token cross-entropy for one microbatch (llama.loss_fn math)."""
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - tgt)


def make_pipeline_loss(num_microbatches: int):
    """A drop-in `loss` for make_train_step running the GPipe schedule.

    Signature matches model.loss_fn: (cfg, params, tokens, *, mesh,
    context_parallel, rope_cache) -> scalar.  `tokens` is the GLOBAL batch;
    it is split into `num_microbatches` along dim 0.
    """

    def loss(cfg, params, tokens, *, mesh: Mesh, context_parallel=False,
             rope_cache=None, loss_mask=None):
        if context_parallel:
            raise NotImplementedError(
                "context parallelism inside pipeline stages is not wired yet "
                "(use context= on a pipeline=1 mesh)")
        if loss_mask is not None:
            raise NotImplementedError("loss_mask with pipeline parallelism")
        pp = mesh.shape["pipeline"]
        m = num_microbatches
        b, s = tokens.shape
        if b % m:
            raise ValueError(f"batch {b} not divisible by microbatches {m}")
        if cfg.n_layers % pp:
            raise ValueError(
                f"n_layers={cfg.n_layers} not divisible by pipeline={pp}")
        if rope_cache is None:
            from ray_tpu.ops.rope import rope_frequencies

            cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                        cfg.rope_theta)
            cos, sin = jnp.asarray(cos), jnp.asarray(sin)
        else:
            cos, sin = rope_cache
        cdt = cfg.compute_dtype
        tokens_mb = tokens.reshape(m, b // m, s)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])

        def stage_block(layers_local, x):
            """Apply this stage's layer block to one microbatch [mb, S, D]."""

            def body(x, lp):
                return llama._layer(cfg, x, lp, cos[:s], sin[:s], None,
                                    False), None

            x, _ = lax.scan(body, x, layers_local)
            return x

        stage_block = jax.checkpoint(stage_block)

        def staged(layers_sharded, embed, final_norm, head, tokens_mb):
            # inside shard_map over {"pipeline"}: layers_sharded leaves are
            # this stage's [L/pp, ...] block; everything else full-size
            idx = lax.axis_index("pipeline")
            is_first = idx == 0
            is_last = idx == pp - 1
            mb = tokens_mb.shape[1]
            buf0 = jnp.zeros((mb, s, cfg.dim), cdt)
            perm = [(i, (i + 1) % pp) for i in range(pp)]

            def tick(carry, t):
                buf, loss_sum, n = carry
                # stage 0 ingests microbatch t while it exists
                tok_in = tokens_mb[jnp.clip(t, 0, m - 1)]
                x_in = jnp.take(embed, tok_in, axis=0).astype(cdt)
                x = jnp.where(is_first, x_in, buf)
                y = stage_block(layers_sharded, x)
                # the microbatch leaving the LAST stage at tick t entered at
                # tick t - (pp - 1)
                mb_id = t - (pp - 1)
                valid = is_last & (mb_id >= 0) & (mb_id < m)
                tok_out = tokens_mb[jnp.clip(mb_id, 0, m - 1)]
                z = rms_norm(y, final_norm, cfg.rms_norm_eps)
                logits = (z @ head.astype(cdt)).astype(jnp.float32)
                l = _ce_loss(cfg, logits, tok_out)
                loss_sum = loss_sum + jnp.where(valid, l, 0.0)
                n = n + valid.astype(jnp.int32)
                buf = lax.ppermute(y, "pipeline", perm)
                return (buf, loss_sum, n), None

            init = (buf0, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))
            # the carry becomes device-varying through ppermute/axis_index;
            # the initial values must carry the same vma type
            init = jax.tree.map(
                lambda x: lax.pcast(x, ("pipeline",), to="varying"), init)
            (_, loss_sum, n), _ = lax.scan(
                tick, init, jnp.arange(m + pp - 1))
            total = lax.psum(loss_sum, "pipeline")
            count = lax.psum(n, "pipeline")
            return total / count.astype(jnp.float32)

        layer_specs = jax.tree.map(
            lambda a: P(*(("pipeline",) + (None,) * (a.ndim - 1))),
            params["layers"])
        return jax.shard_map(
            staged,
            mesh=mesh,
            axis_names={"pipeline"},
            in_specs=(layer_specs, P(), P(), P(), P()),
            out_specs=P(),
        )(params["layers"], params["embed"], params["final_norm"], head,
          tokens_mb)

    return loss
