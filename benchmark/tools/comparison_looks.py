#!/usr/bin/env python3
"""Two looks at what ``compare.py``'s numbers are made of, for a
configuration whose reference routes tokens to experts
(``ling_linear_moe_reference``: it reaches for that module's ``route``,
``_layer`` and ``_product``). No run of the benchmark makes them; PERF.md
section 2 has the readings.

    python3 benchmark/tools/comparison_looks.py pinned \
        --config benchmark/configs/ling-3.0-flash-vl-ep64.json --seq-len 512 --seed 7
    python3 benchmark/tools/comparison_looks.py rows_left_out --config ... --seq-len 4096 --seed 7

``pinned``: the program against the reference on the same weights, once
as ``compare.py`` runs it and once with the PROGRAM's selection of
experts, in every expert layer, pinned to the one the reference made
there (the gates still the program's own, from its own scores). What is
left with the selections pinned is what rounding alone does to a
gradient leaf; what goes away is what flipped selections did. Also the
share of the reference's assignments the program does not make, layer
by layer.

``rows_left_out``: ``compare.py``'s comparison with the program's loss
taken over the first sequence of the batch alone, the fault
``tests/test_rehearsal.py`` breaks the timed path with: what the
comparison reads for it beside the reference's limits.

``--set key=value`` overrides a model parameter (a cut size for the
CPU). One JSON line on stdout."""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import compare  # noqa: E402
import spec  # noqa: E402


def _group(name):
    leaf = name.split(".")[-1]
    if leaf == "router":
        return "routers"
    if leaf.startswith("expert_"):
        return "routed_experts"
    if leaf in ("wf", "a_log", "dt_bias"):
        return "kda_gates"
    return name if name in ("embed", "head", "final_norm") else "other"


def _by_group(rel):
    groups = {}
    for name, value in rel.items():
        low, high = groups.get(_group(name), (value, value))
        groups[_group(name)] = (min(low, value), max(high, value))
    return {group: list(span) for group, span in sorted(groups.items())}


def pinned(config, seq_len, seed):
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.parallel import expert

    sizes = config["model_params"]
    reference = spec.load_reference(config["reference"])
    model, program_loss = compare._load_program_model(config)
    # the same model with no layer under jax.checkpoint: a selection
    # looked at from outside may not leave a rematerialised trace
    open_model, _ = compare._load_program_model(
        dict(config, model_params=dict(sizes, remat_layers=False))
    )
    key_init, key_batch = jax.random.split(jax.random.PRNGKey(seed))
    tokens = jax.random.randint(
        key_batch, (compare.BATCH, seq_len), 0, sizes["vocab_size"]
    )
    params = jax.jit(lambda key, t: model.init(key, {"tokens": t})["params"])(
        key_init, tokens
    )
    pattern = sizes["layer_pattern"]
    expert_layers = len(pattern) - sizes["num_dense_layers"]

    @jax.jit
    def reference_selection(params, tokens):
        """(expert layers, B * L, E) bool: what the reference selects,
        from its own forward pass written out flat."""
        weights = reference.from_program(params, sizes)
        product = reference._product(lambda x: x)
        chosen, plain = [], reference.route

        def watched(u, router, sizes, product):
            gates = plain(u, router, sizes, product)
            chosen.append(gates > 0)
            return gates

        reference.route = watched
        try:
            with jax.default_matmul_precision("highest"):
                for row in range(tokens.shape[0]):
                    x = weights["embed"][tokens[row]][None]
                    for i, kind in enumerate(pattern):
                        prefix = "L%d." % i
                        w = {
                            name[len(prefix) :]: value
                            for name, value in weights.items()
                            if name.startswith(prefix)
                        }
                        x = reference._layer(
                            x, w, kind, i < sizes["num_dense_layers"], sizes, product
                        )
        finally:
            reference.route = plain
        assert len(chosen) == expert_layers * tokens.shape[0]
        return jnp.stack(
            [
                jnp.concatenate(
                    [chosen[row * expert_layers + i][0] for row in range(tokens.shape[0])]
                )
                for i in range(expert_layers)
            ]
        )

    def with_route(route, run):
        plain = expert.sigmoid_topk_route
        expert.sigmoid_topk_route = route
        try:
            return run()
        finally:
            expert.sigmoid_topk_route = plain

    @jax.jit
    def flipped(params, tokens, selection):
        seen, plain = [], expert.sigmoid_topk_route

        def watched(*args, **kwargs):
            seen.append(plain(*args, **kwargs))
            return seen[-1]

        with_route(
            watched,
            lambda: open_model.apply({"params": params}, {"tokens": tokens}, training=True),
        )
        return jnp.stack(
            [
                1.0 - jnp.mean(jnp.take_along_axis(selection[i], selected, axis=-1))
                for i, (selected, _) in enumerate(seen[:expert_layers])
            ]
        )

    def gradients(params, tokens, selection=None):
        calls = []

        def pinned_route(logits, bias, k, scaling=1.0, n_group=1, topk_group=1):
            # a rematerialised layer is traced again, in the same order
            layer = len(calls) % expert_layers
            calls.append(layer)
            scores = jax.nn.sigmoid(logits.astype(jnp.float32))
            _, selected = jax.lax.top_k(selection[layer].astype(jnp.float32), k)
            picked = jnp.take_along_axis(scores, selected, axis=-1)
            gates = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)
            return selected.astype(jnp.int32), gates * scaling

        def objective(params):
            logits = model.apply({"params": params}, {"tokens": tokens}, training=True)
            return program_loss(logits, tokens).astype(jnp.float32)

        run = lambda: jax.grad(objective)(params)  # noqa: E731
        grads = run() if selection is None else with_route(pinned_route, run)
        return reference.from_program(grads, sizes)

    selection = reference_selection(params, tokens)
    _, wanted = jax.jit(
        lambda p, t: reference.loss_and_grads(reference.from_program(p, sizes), t, sizes)
    )(params, tokens)
    out = {
        "look": "pinned",
        "config": config["name"],
        "seq_len": seq_len,
        "seed": seed,
        "platform": jax.devices()[0].platform,
        "flipped_share_by_layer": [float(v) for v in flipped(params, tokens, selection)],
    }
    for label, pin in (("as_compared", None), ("selection_pinned", selection)):
        got = jax.jit(gradients)(params, tokens, pin)
        rel = {
            name: float(
                jnp.linalg.norm((got[name] - wanted[name]).ravel())
                / jnp.linalg.norm(wanted[name].ravel())
            )
            for name in wanted
        }
        worst = max(rel, key=rel.get)
        out[label] = {"worst_leaf": worst, "worst": rel[worst], "by_group": _by_group(rel)}
    return out


def rows_left_out(config, seq_len, seed):
    plain = compare._load_program_model

    def broken(config):
        model, loss = plain(config)
        return model, lambda output, labels: loss(output[:1], labels[:1])

    compare._load_program_model = broken
    try:
        got = compare.compare(config, seq_len, seed)
    finally:
        compare._load_program_model = plain
    rel = got.pop("grad_rel_l2_error")
    limit = got["grad_rel_l2_tolerance"]
    smallest = min(rel, key=rel.get)
    return dict(
        got,
        look="rows_left_out",
        leaves=len(rel),
        leaves_over_the_limit=sum(v > limit for v in rel.values()),
        smallest_leaf=smallest,
        smallest=rel[smallest],
        by_group=_by_group(rel),
    )


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("look", choices=["pinned", "rows_left_out"])
    parser.add_argument("--config", required=True)
    parser.add_argument("--seq-len", type=int, required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--set", action="append", default=[], metavar="KEY=VALUE")
    args = parser.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)
    for pair in args.set:
        key, value = pair.split("=", 1)
        config["model_params"][key] = json.loads(value)
    look = pinned if args.look == "pinned" else rows_left_out
    print(json.dumps(look(config, args.seq_len, args.seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
