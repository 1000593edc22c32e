"""The system under test for the CNN configurations: the program's
federation engine, selection and CNN, built through their normal path
(``init_server_state`` -> ``make_round_fn`` -> ``run_scanned`` /
``run_many``), as ``chip_smoke.paper_federation`` builds it.

Two departures, both forced by the program and kept to the harness's side:

* The round function gets no ``accuracy_fn``: the engine closes over
  ``eval_data``, and ``jax.jit`` embeds closed-over arrays in the program as
  constants, so the test set would be compiled into it and every seed would
  compile anew.  The harness calls the program's own ``cnn.accuracy`` on the
  held-out set after each chunk instead, which is also where the stopping
  rule reads it.
* A lockstep batch's accuracy is read one federation at a time.

The deployment is ``bench/data.py``'s synthetic images; ``TINY_SIZES`` cut
the configuration for the CPU tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import make_strategy
from repro.fl import engine
from repro.models import cnn

from bench import flops
from bench.data import make_deployment

__all__ = ["TINY_SIZES", "make_deployment", "accuracy", "System"]

# every width and count shrunk; the protocol keys are left as they are
TINY_SIZES = {"num_clients": 12, "clients_per_round": 3, "samples_per_client": 20,
              "num_classes": 4, "channels": [4, 8], "fc1_dim": 16, "test_samples": 64,
              "max_rounds": 20, "target_accuracy": 0.4}


def accuracy(params, test_xs, test_ys):
    """One federation's held-out accuracy, as the program reads it."""
    return cnn.accuracy(params, test_xs, test_ys)


class System:
    """One configuration's federation under one selection strategy."""

    def __init__(self, cfg: dict, strategy: str):
        self.cfg = cfg
        self.fl = engine.FLConfig(
            num_clients=cfg["num_clients"],
            clients_per_round=cfg["clients_per_round"],
            local_epochs=cfg["local_epochs"],
            lr=cfg["lr"],
            rounds=cfg["max_rounds"],
            eval_every=cfg["eval_every"],
            num_classes=cfg["num_classes"],
            use_pallas_kernel=cfg["use_pallas_kernel"],
        )
        self.strategy = make_strategy(strategy)
        self.round_fn = engine.make_round_fn(self.fl, cnn.cnn_loss, (self.strategy,))

    def init_params(self, key):
        return cnn.init_cnn(
            key, num_classes=self.cfg["num_classes"], in_hw=tuple(self.cfg["image_hw"]),
            channels=tuple(self.cfg["channels"]), fc1_dim=self.cfg["fc1_dim"],
        )

    def init_state(self, params, key, client_xs, client_ys):
        return engine.init_server_state(
            self.fl, params, cnn.cnn_loss, cnn.apply_with_features,
            client_xs, client_ys, strategy=self.strategy, key=key,
        )

    def stack(self, states):
        return engine.stack_states(states)

    def run_chunk(self, state, rounds: int, lockstep: int):
        if lockstep == 1:
            return engine.run_scanned(self.round_fn, state, rounds)
        return engine.run_many(self.round_fn, state, rounds)

    def accuracy(self, params, test_xs, test_ys, lockstep: int):
        """Held-out accuracy of each federation, one ``cnn.accuracy`` call
        per federation of a lockstep batch: on the chip, ``jax.vmap`` of it
        over the batch's params read chance for most federations whose
        weights matched the reference (PERF.md, findings of PR 13)."""
        if lockstep == 1:
            return accuracy(params, test_xs, test_ys)
        return jnp.stack([
            accuracy(jax.tree_util.tree_map(lambda x, s=s: x[s], params), test_xs, test_ys)
            for s in range(lockstep)])

    # ---------------------------------------------------- counted from shapes
    def round_flops(self, eval_round: bool) -> int:
        return flops.round_flops(self.cfg, eval_round)

    def init_flops(self) -> int:
        return flops.init_flops(self.cfg)

    def eq14_kernels(self) -> dict:
        return flops.eq14_kernels(self.cfg["num_clients"], self.cfg["fc1_dim"])

