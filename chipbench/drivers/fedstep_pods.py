"""Driver of the pod-step cells with several pods, one per chip, that
gossip: ``drivers/fedstep.py``'s session (the program's step, its gossip
over the ``pod`` axis included), with the plain reference of every pod and
of the ring's mixing, its wire replayed (``refs/fedstep_pods_ref.py``), in
place of the one-pod reference."""
from __future__ import annotations

import jax
import numpy as np

from chipbench import traffic
from chipbench.drivers import fedstep
from chipbench.refs import fedstep_pods_ref, models


class Session(fedstep.Session):
    def __init__(self, cfg: dict, wl: dict, seed: int, chips: int):
        self.seed, self.chips = seed, chips
        super().__init__(cfg, wl, seed, chips)

    def reference(self, **kw) -> dict:
        """The plain reference's readings of the checked calls, pod i on chip
        i; ``kw`` (``dtype``, ``precision``, ``batch_frac``) goes to the
        reference. The step keys split from the session's second key draw."""
        rng = np.random.default_rng(self.seed)
        traffic.key_words(rng)                       # the weights' key
        root = jax.numpy.asarray(traffic.key_words(rng))
        init = jax.jit(lambda k: models.init(self.cfg, k))
        return fedstep_pods_ref.run(self.cfg, self.t, lambda: init(self.wkey), self.batches,
                                    root, jax.devices()[:self.chips], **kw)
