"""One member of tests/test_elastic_donation.py's two-process world.

``python elastic_donation_world.py <pid> <port_alone> <port_pair>``

Rank 0 first trains alone (a world of one process: its mesh is
process-local, the step donates), pauses the way the worker does at an
aligned sync point (validate, snapshot, leave), and then forms a world
of two with rank 1, a fresh joiner. There the mesh spans processes:
the step built for it donates nothing, the state rank 0 carried over
reaches both, and the input of a step survives it. The last stdout
line is this member's account as JSON."""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["EDL_DIST_PLATFORM"] = "cpu"
os.environ["EDL_LOCAL_DEVICES"] = "1"
# rank 1 waits in the barrier while rank 0 trains alone
os.environ["EDL_WORLD_INIT_TIMEOUT"] = "240"
os.environ["EDL_SHUTDOWN_TIMEOUT"] = "5"
os.environ.pop("XLA_FLAGS", None)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import optax  # noqa: E402

import jax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from elasticdl_tpu.parallel.distributed import WorldSpec  # noqa: E402
from elasticdl_tpu.parallel.elastic import (  # noqa: E402
    ElasticDPTrainer,
    count_donated_inputs,
    host_copy,
    make_pjit_train_step,
)
from model_zoo.transformer_lm import transformer_lm as tzoo  # noqa: E402

KW = dict(
    vocab_size=32,
    num_layers=1,
    num_heads=2,
    head_dim=8,
    embed_dim=16,
    mlp_dim=32,
    use_flash=False,
)
ROWS = 4
ALONE_STEPS = 3
PAIR_STEPS = 2


def _batch(seed):
    toks = np.random.default_rng(seed).integers(0, 32, (ROWS, 8))
    toks = toks.astype(np.int32)
    return {"tokens": toks}, toks.copy()


def _deleted(ts):
    return [leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(ts)]


def main(pid, port_alone, port_pair):
    out = {"pid": pid}
    model = tzoo.custom_model(**KW)
    trainer = ElasticDPTrainer(model, tzoo.loss, optax.sgd(0.05))
    trainer.default_minibatch_size = ROWS
    batch = _batch(0)
    if pid == 0:
        trainer.establish(
            WorldSpec("localhost:%d" % port_alone, 1, 0, 1),
            example_batch=batch,
        )
        out["alone_multi_process"] = trainer.mesh.is_multi_process
        out["alone_donated_inputs"] = trainer.describe_step()[
            "donated_inputs"
        ]
        out["leaves"] = len(jax.tree_util.tree_leaves(trainer._ts))
        given = trainer._ts
        for i in range(ALONE_STEPS):
            trainer.train_step(*_batch(i), ROWS, sync=False)
        out["alone_input_deleted"] = all(_deleted(given))
        # the worker's aligned pause: validate, snapshot, leave
        out["alone_validated"] = trainer.validate()
        out["alone_checked_kept"] = trainer._checked_ts is not None
        trainer.leave()
        out["alone_version"] = int(np.asarray(trainer._host_ts.version))
    trainer.establish(
        WorldSpec("localhost:%d" % port_pair, 2, pid, 2),
        example_batch=batch,
    )
    mesh = trainer.mesh
    out["pair_multi_process"] = mesh.is_multi_process
    out["pair_donated_inputs"] = trainer.describe_step()["donated_inputs"]
    out["pair_version_carried"] = trainer.version
    # the GSPMD builder on the same mesh, by the same rule
    abstract = trainer._abstract_step_args(mesh, batch)
    specs = jax.tree_util.tree_map(lambda _: P(), abstract[0])
    pjit_step = make_pjit_train_step(
        model, tzoo.loss, optax.sgd(0.05), mesh, specs
    )
    with mesh:
        lowered = pjit_step.lower(*abstract).as_text()
    out["pair_pjit_donated_inputs"] = count_donated_inputs(lowered)
    given = trainer._ts
    for i in range(PAIR_STEPS):
        trainer.train_step(*_batch(10 + i + pid), ROWS, sync=True)
    out["pair_input_deleted"] = any(_deleted(given))
    out["pair_checked_is_newest"] = trainer._checked_ts is trainer._ts
    out["pair_version"] = trainer.version
    # the worker's aligned sync: two steps dispatched, the one before
    # the newest validated. The rollback state is the VALIDATED step's
    # (this mesh donates nothing, so it is alive), not the newest's
    for i in range(2):
        trainer.train_step(*_batch(20 + i + pid), ROWS, sync=False)
    out["lagged_losses"] = len(trainer.settle(lag=1))
    out["lagged_in_flight"] = trainer.steps_in_flight
    out["lagged_checked_is_newest"] = trainer._checked_ts is trainer._ts
    out["lagged_checked_version"] = int(
        host_copy(trainer._checked_ts.version)
    )
    out["lagged_checked_deleted"] = any(_deleted(trainer._checked_ts))
    out["lagged_validated_version"] = trainer.validated_version
    out["lagged_newest_version"] = trainer.version
    out["pair_validated"] = trainer.validate()
    out["settled_losses"] = len(trainer.drain_metrics())
    trainer.leave()
    trainer.close()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(*(int(a) for a in sys.argv[1:4]))
