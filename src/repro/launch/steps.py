"""train_step / prefill_step / decode_step builders with full sharding specs.

These are the jit roots: everything the dry-run lowers and the trainer runs.
Each builder returns (fn, in_shardings, out_shardings, abstract_inputs) so
callers can either execute or ``jax.jit(fn, ...).lower(...)``.

Gradient fusion across the 'pod' axis optionally runs through the paper's
lossy compression (core/compression.compressed_psum) inside a partial-manual
shard_map (manual: pod; auto: data/model) — wire bytes drop 4x (int8) or 8x
(int4) on exactly the links where the paper's technique targets its savings.
The installed XLA aborts on that pattern (``compat.PARTIAL_MANUAL_SHARD_MAP``),
so ``build_train_step`` refuses it instead of handing XLA a program that
kills the process.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..compat import PARTIAL_MANUAL_SHARD_MAP
from ..configs.base import ModelConfig, ShapeSpec
from ..core.compression import QuantConfig, compressed_psum
from ..models import chunked_xent_loss, get_model, lm_logits
from ..optim import AdamWConfig, adamw_init, adamw_update, opt_state_specs
from ..sharding import logical_spec, make_rules, use_sharding

__all__ = ["TrainStepConfig", "build_train_step", "build_serve_step"]


@dataclasses.dataclass(frozen=True)
class TrainStepConfig:
    microbatches: int = 1
    compression_bits: int | None = None   # None = exact bf16 fusion over pod
    remat: bool = True
    zero1: bool = True
    adamw: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)
    moe_groups: int = 16
    strategy: str = "tp"                  # 'tp' | 'fsdp' (see make_rules)


def _rules_with_zero(cfg, mesh, mode, decode_batch=None, strategy="tp"):
    rules = make_rules(cfg, mesh, mode, decode_batch, strategy=strategy)
    zero = tuple(a for a in ("pod", "data") if a in mesh.shape)
    if strategy == "fsdp" and "model" in mesh.shape:
        zero = zero + ("model",)
    rules["zero"] = zero or None
    return rules


def _strip_pod(rules):
    """Rules for code running inside a manual-'pod' shard_map body."""
    out = {}
    for k, v in rules.items():
        if isinstance(v, (tuple, list)):
            v = tuple(a for a in v if a != "pod") or None
            if isinstance(v, tuple) and len(v) == 1:
                v = v[0]
        elif v == "pod":
            v = None
        out[k] = v
    return out


def _shardings_for(tree_specs, shapes, mesh):
    out = {}
    for k, axes in tree_specs.items():
        out[k] = NamedSharding(mesh, logical_spec(axes, shapes[k]))
    return out


def build_train_step(cfg: ModelConfig, mesh, shape: ShapeSpec,
                     tcfg: TrainStepConfig = TrainStepConfig()):
    """Returns (train_step, state_shardings, input_shardings, abstract args)."""
    model = get_model(cfg)
    rules = _rules_with_zero(cfg, mesh, "train", strategy=tcfg.strategy)
    pod_axis = "pod" in mesh.shape
    n_pods = mesh.shape.get("pod", 1)

    schema = model.schema
    param_shapes = {k: ps.shape for k, ps in schema.items()}
    p_specs = model.param_specs()

    with use_sharding(mesh, rules):
        param_sh = _shardings_for(p_specs, param_shapes, mesh)
        o_specs = opt_state_specs(p_specs, mesh, param_shapes, tcfg.zero1)
        opt_sh = {
            "master": _shardings_for(o_specs["master"], param_shapes, mesh),
            "m": _shardings_for(o_specs["m"], param_shapes, mesh),
            "v": _shardings_for(o_specs["v"], param_shapes, mesh),
            "step": NamedSharding(mesh, P()),
        }
        batch_sh = NamedSharding(mesh, logical_spec(
            ("batch", "seq"), (shape.global_batch, shape.seq_len)))
        grad_acc_specs = o_specs["m"]  # ZeRO-sharded fp32 accumulator

    aux_abstract = model.aux_inputs(shape.global_batch, shape.seq_len)
    with use_sharding(mesh, rules):
        aux_sh = {k: NamedSharding(mesh, logical_spec(("batch", None, None), v.shape))
                  for k, v in aux_abstract.items()}

    inner_rules = _strip_pod(rules) if pod_axis else rules

    def loss_fn(params, tokens, labels, aux):
        hidden, _ = model.forward(params, tokens, cfg, mode="train",
                                  remat=tcfg.remat, n_groups=tcfg.moe_groups,
                                  **aux)
        return chunked_xent_loss(params, hidden, labels, cfg)

    def grads_microbatched(params, tokens, labels, aux, rules_in):
        """Gradient accumulation over microbatches (fp32, ZeRO-sharded).

        Each microbatch's fp32 grads are constrained to the ZeRO ('zero'
        axis) sharding *at production* — XLA reduce-scatters per leaf instead
        of materializing the full fp32 gradient (at 47B params that fp32
        transient alone is 11.7 GB/device)."""
        mb = tcfg.microbatches

        def rs(tree):
            # constrain in the gradient's native bf16 *first* (the transient
            # full-size buffer stays 2 bytes/elem), cast to fp32 after the
            # reduce-scatter when the per-device shard is 'zero'-sized
            with use_sharding(mesh, rules_in):
                out = {}
                for k, v in tree.items():
                    sh = NamedSharding(mesh, logical_spec(
                        grad_acc_specs[k], param_shapes[k]))
                    v = jax.lax.with_sharding_constraint(v, sh)
                    out[k] = v.astype(jnp.float32)
                return out

        with use_sharding(mesh, rules_in):
            if mb == 1:
                loss, grads = jax.value_and_grad(loss_fn)(params, tokens,
                                                          labels, aux)
                return loss, rs(grads)
            b = tokens.shape[0]
            tok = tokens.reshape(mb, b // mb, -1)
            lab = labels.reshape(mb, b // mb, -1)
            aux_r = {k: v.reshape(mb, b // mb, *v.shape[1:])
                     for k, v in aux.items()}

            def body(carry, xs):
                acc, loss_acc = carry
                tk, lb = xs[0], xs[1]
                aux_i = {k: xs[2 + i] for i, k in enumerate(sorted(aux_r))}
                loss, grads = jax.value_and_grad(loss_fn)(params, tk, lb, aux_i)
                grads = rs(grads)
                acc = {k: acc[k] + grads[k] for k in acc}
                return (acc, loss_acc + loss), ()

            acc0 = rs({k: jnp.zeros(param_shapes[k], jnp.bfloat16)
                       for k in params})
            xs = (tok, lab) + tuple(aux_r[k] for k in sorted(aux_r))
            (grads, loss_sum), _ = jax.lax.scan(body, (acc0, jnp.zeros(())), xs)
            inv = 1.0 / mb
            return loss_sum * inv, {k: g * inv for k, g in grads.items()}

    # the manual-'pod' shard_map exists only to make the *compressed* fusion
    # expressible (int8/int4 collectives in HLO). Uncompressed multi-pod
    # fusion is plain GSPMD: XLA inserts the exact pod all-reduce itself —
    # this is also the paper-faithful "32-bit fusion" baseline. (The MoE
    # dispatch scatter inside a manual-axis shard_map trips an XLA SPMD
    # partitioner CHECK at 512 devices — see EXPERIMENTS.md §Dry-run notes —
    # so MoE archs currently fuse uncompressed across pods.)
    if pod_axis and tcfg.compression_bits is not None:
        if not PARTIAL_MANUAL_SHARD_MAP:
            raise NotImplementedError(
                "compressed pod-axis gradient fusion needs partial-manual "
                "shard_map, which the XLA SPMD partitioner aborts on "
                "(compat.PARTIAL_MANUAL_SHARD_MAP); use "
                "compression_bits=None")
        qc = QuantConfig(bits=tcfg.compression_bits)

        def pod_body(params, tokens, labels, aux):
            loss, grads = grads_microbatched(params, tokens, labels, aux,
                                             inner_rules)
            fused, noise = {}, jnp.zeros(())
            for k in sorted(grads):
                fused[k], nv = compressed_psum(grads[k], "pod", qc)
                noise = noise + nv
            grads = {k: v / n_pods for k, v in fused.items()}
            loss = jax.lax.psum(loss, "pod") / n_pods
            return loss, grads, noise

        # partial-manual shard_map: specs may only mention the manual axis
        # ('pod'); data/model sharding stays under GSPMD control (auto).
        pod_grads = jax.shard_map(
            pod_body, mesh=mesh,
            in_specs=({k: P() for k in p_specs},
                      P("pod", None), P("pod", None),
                      {k: P("pod", None, None) for k in aux_abstract}),
            out_specs=(P(), {k: P() for k in p_specs}, P()),
            axis_names={"pod"}, check_vma=False)
    else:
        def pod_grads(params, tokens, labels, aux):  # single-pod: plain GSPMD
            loss, grads = grads_microbatched(params, tokens, labels, aux, rules)
            return loss, grads, jnp.zeros(())

    def train_step(params, opt_state, tokens, labels, aux):
        loss, grads, noise = pod_grads(params, tokens, labels, aux)
        with use_sharding(mesh, rules):
            new_params, new_opt, metrics = adamw_update(
                params, grads, opt_state, tcfg.adamw)
        metrics = dict(metrics, loss=loss, quant_noise=noise)
        return new_params, new_opt, metrics

    abstract = {
        "params": {k: jax.ShapeDtypeStruct(ps.shape, jnp.bfloat16)
                   for k, ps in schema.items()},
        "tokens": jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len),
                                       jnp.int32),
        "labels": jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len),
                                       jnp.int32),
        "aux": aux_abstract,
    }
    opt_abstract = {
        "master": {k: jax.ShapeDtypeStruct(s, jnp.float32)
                   for k, s in param_shapes.items()},
        "m": {k: jax.ShapeDtypeStruct(s, jnp.float32)
              for k, s in param_shapes.items()},
        "v": {k: jax.ShapeDtypeStruct(s, jnp.float32)
              for k, s in param_shapes.items()},
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    abstract["opt_state"] = opt_abstract

    shardings = {
        "params": param_sh, "opt_state": opt_sh,
        "tokens": batch_sh, "labels": batch_sh, "aux": aux_sh,
    }
    return train_step, shardings, abstract


def build_serve_step(cfg: ModelConfig, mesh, shape: ShapeSpec,
                     moe_groups: int = 16):
    """Prefill or decode step per shape.kind. Returns (fn, shardings, abstract)."""
    model = get_model(cfg)
    b, s = shape.global_batch, shape.seq_len
    mode = "decode" if shape.kind == "decode" else "prefill"
    rules = _rules_with_zero(cfg, mesh, mode,
                             decode_batch=b if mode == "decode" else None)
    schema = model.schema
    param_shapes = {k: ps.shape for k, ps in schema.items()}
    p_specs = model.param_specs()

    with use_sharding(mesh, rules):
        param_sh = _shardings_for(p_specs, param_shapes, mesh)
        state_abstract = jax.eval_shape(lambda: model.init_state(cfg, b, s))
        state_sh = jax.tree.map(
            lambda x: NamedSharding(mesh, _state_spec(x.shape, rules, mesh)),
            state_abstract)

    aux_abstract = model.aux_inputs(b, s)
    with use_sharding(mesh, rules):
        aux_sh = {k: NamedSharding(mesh, logical_spec(("batch", None, None), v.shape))
                  for k, v in aux_abstract.items()}
        tok_sh_full = NamedSharding(mesh, logical_spec(("batch", "seq"), (b, s)))
        tok_sh_one = NamedSharding(mesh, logical_spec(("batch", "seq"), (b, 1)))

    if mode == "prefill":
        tok_sh = tok_sh_full

        def prefill_step(params, tokens, aux):
            with use_sharding(mesh, rules):
                hidden, caches = model.forward(params, tokens, cfg,
                                               mode="prefill", remat=False,
                                               n_groups=moe_groups, **aux)
                logits = lm_logits(params, hidden[:, -64:], cfg)
            return logits, caches

        abstract = {"params": {k: jax.ShapeDtypeStruct(ps.shape, jnp.bfloat16)
                               for k, ps in schema.items()},
                    "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
                    "aux": aux_abstract}
        return prefill_step, {"params": param_sh, "tokens": tok_sh,
                              "aux": aux_sh}, abstract

    tok_sh = tok_sh_one

    def decode_step(params, tokens, state, pos):
        with use_sharding(mesh, rules):
            hidden, new_state = model.decode_step(params, tokens, state, pos,
                                                  cfg, n_groups=moe_groups)
            logits = lm_logits(params, hidden, cfg)
        return logits, new_state

    abstract = {"params": {k: jax.ShapeDtypeStruct(ps.shape, jnp.bfloat16)
                           for k, ps in schema.items()},
                "tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32),
                "state": state_abstract,
                "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    return decode_step, {"params": param_sh, "tokens": tok_sh,
                         "state": state_sh,
                         "pos": NamedSharding(mesh, P())}, abstract


def _state_spec(shape, rules, mesh):
    """Heuristic cache/state PartitionSpec: (layers, batch, seq, kv, dh) or
    recurrent-state layouts; batch -> data when divisible, seq -> kv_seq rule."""
    from ..sharding import _axis_size  # noqa

    ndim = len(shape)
    if ndim >= 3:
        # (L, B, S, ...) caches and (L, B, ...) states
        names = ["layers", "batch"]
        if ndim >= 4:
            names.append("kv_seq")
            names += [None] * (ndim - 3)
        else:
            names += [None] * (ndim - 2)
    else:
        names = [None] * ndim
    return logical_spec(names, shape)
