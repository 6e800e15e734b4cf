#!/usr/bin/env python3
"""The comparison child: the program's model against the plain reference.

    python3 benchmark/compare.py --config benchmark/configs/lm-125m.json \
        --seq-len 2048 --seed 7

Runs in a process of its own, after the job's worker has exited and
freed the chip, on one device. It builds the model with the program's
own ``custom_model(**model_params)`` and ``loss``, initialises it from
the seed, draws one seeded batch of BATCH sequences of the cell's length
at the published widths, and compares the loss and every gradient leaf
with ``benchmark/reference/lm_reference.py`` on the same weights. One
JSON line on stdout; exit code 0 whether or not they agree (the caller
reads ``agree``), non-zero only if the comparison could not be made.
"""

import argparse
import importlib
import json
import os
import sys
import time

T0 = time.time()  # jax and the program are imported inside compare()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

BATCH = 2

# The tolerances, and why. The configurations compute in bfloat16 (8
# significant bits: one rounding is up to 2^-9 = 0.2% relative) with f32
# parameters and f32 accumulation; the reference is f32 at highest
# precision. Each gradient leaf is compared by its relative L2 error,
# |g - g_ref| / |g_ref|, over the whole leaf. Independent roundings of
# every matmul operand and activation, through 12 to 24 residual layers
# forward and back, add up to about 1%: measured on the v5e at the
# published widths, 0.5% (final norm scale) to 1.9% (query and key
# projections, 24 layers) a leaf, the same at L=512 and L=2048 (PERF.md
# section 6 lists them). The tolerance is twice the worst leaf seen.
# A dropped or wrong term (a missing bias gradient, an unmasked position,
# a missing 1/sqrt(hd), no rotary) moves a leaf by tens of percent; a
# path that accumulates in bf16 where the configuration accumulates in
# f32 (a 768- to 4096-term dot product summed in 8 bits) loses several
# percent. What the tolerance cannot see is a single extra bf16 rounding
# of one tensor. The loss: the program returns it in bf16 (the tied
# head's logits come out in the module's dtype), so it is held to one
# bf16 spacing at the bottom of a binade, 2^-7; the mean over thousands
# of positions is otherwise far more exact than that.
GRAD_REL_L2_TOL = 0.04
LOSS_REL_TOL = 2.0**-7


def _load_program_model(config):
    sys.path.insert(0, os.path.join(ROOT, "model_zoo"))
    sys.path.insert(0, ROOT)
    module_name, fn_name = config["model_def"].rsplit(".", 1)
    module = importlib.import_module(module_name)
    return getattr(module, fn_name)(**config["model_params"]), module.loss


def compare(config, seq_len, seed):
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, HERE)
    from reference import lm_reference

    model, program_loss = _load_program_model(config)
    num_layers = config["model_params"]["num_layers"]
    key_init, key_batch = jax.random.split(jax.random.PRNGKey(seed))
    # the job's skewed unigram would leave most embedding rows without
    # a gradient; uniform ids touch the table evenly
    tokens = jax.random.randint(
        key_batch, (BATCH, seq_len), 0, config["model_params"]["vocab_size"]
    )

    @jax.jit
    def init(key, tokens):
        return model.init(key, {"tokens": tokens})["params"]

    def program_objective(params, tokens):
        logits = model.apply(
            {"params": params}, {"tokens": tokens}, training=True
        )
        return program_loss(logits, tokens).astype(jnp.float32)

    @jax.jit
    def errors(params, tokens):
        loss, grads = jax.value_and_grad(program_objective)(params, tokens)
        weights = lm_reference.stack_program_params(params, num_layers)
        ref_loss, ref_grads = lm_reference.loss_and_grads(weights, tokens)
        got = lm_reference.stack_program_params(grads, num_layers)
        rel = {
            name: jnp.linalg.norm((got[name] - ref_grads[name]).ravel())
            / jnp.linalg.norm(ref_grads[name].ravel())
            for name in ref_grads
        }
        finite = jnp.all(
            jnp.stack([jnp.all(jnp.isfinite(g)) for g in got.values()])
        )
        return loss, ref_loss, rel, finite

    t_ready = time.time()  # imports, the backend, the batch
    params = jax.block_until_ready(init(key_init, tokens))
    t_init = time.time()
    leaves = len(jax.tree_util.tree_leaves(params))
    loss, ref_loss, rel, finite = jax.device_get(errors(params, tokens))
    t_errors = time.time()
    loss, ref_loss = float(loss), float(ref_loss)
    rel = {name: float(v) for name, v in rel.items()}
    loss_rel = abs(loss - ref_loss) / abs(ref_loss)
    device = jax.devices()[0]
    return {
        "agree": bool(
            finite
            and loss_rel <= LOSS_REL_TOL
            and all(v <= GRAD_REL_L2_TOL for v in rel.values())
        ),
        "config": config["name"],
        "seq_len": seq_len,
        "batch": BATCH,
        "program_leaves": leaves,
        "loss": loss,
        "reference_loss": ref_loss,
        "loss_rel_error": loss_rel,
        "loss_rel_tolerance": LOSS_REL_TOL,
        "grad_rel_l2_error": rel,
        "grad_rel_l2_tolerance": GRAD_REL_L2_TOL,
        "platform": device.platform,
        "device_kind": device.device_kind,
        # what the check costs, by phase: process start to a live
        # backend; init and the comparison, each with its trace,
        # lowering and compile or cache load
        "seconds": {
            "start": t_ready - T0,
            "init": t_init - t_ready,
            "errors": t_errors - t_init,
        },
    }


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--seq-len", type=int, required=True)
    parser.add_argument("--seed", type=int, required=True)
    args = parser.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)
    print(json.dumps(compare(config, args.seq_len, args.seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
