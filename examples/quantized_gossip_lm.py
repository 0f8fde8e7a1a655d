"""Pod-scale DFedRW end-to-end: train a small LM with per-group divergent
params, random-walk batch reassignment and (quantized) gossip aggregation
over a simulated 8-device mesh -- numerically, not just lowering.

  python examples/quantized_gossip_lm.py        (sets its own XLA device count)
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.dist.gossip import GossipConfig
from repro.dist.sharding import batch_specs, named
from repro.dist.steps import make_fed_train_step
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.models.config import ArchConfig


def main():
    cfg = ArchConfig(name="tiny-lm", n_layers=2, d_model=64, n_heads=4,
                     n_kv_heads=2, d_ff=128, vocab=256)
    mesh = make_mesh((4, 2, 1), ("pod", "data", "model"))
    g = 4  # federated groups == pod axis
    for quant_bits, tag in [(32, "DFedRW"), (8, "QDFedRW-8b")]:
        gossip = GossipConfig(axis="pod", topology="ring", every=2,
                              quant_bits=quant_bits)
        step_fn, p_specs, fed_abs = make_fed_train_step(cfg, mesh, gossip,
                                                        lr_r=2.0, remat=False)
        key = jax.random.PRNGKey(0)
        base = T.init_params(cfg, key, jnp.float32)
        params = jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l, (g, *l.shape)).copy(), base)
        params = jax.device_put(params, named(p_specs, mesh))
        vel = jax.tree_util.tree_map(jnp.zeros_like, params)
        jitted = jax.jit(step_fn)
        rng = np.random.default_rng(0)
        b, s = 16, 32
        with mesh:
            for step in range(40):
                # structured synthetic data: next = (3*tok + 7) % vocab
                t0 = rng.integers(0, cfg.vocab, size=(g, b, 1))
                seq = [t0]
                for _ in range(s):
                    seq.append((3 * seq[-1] + 7) % cfg.vocab)
                toks = np.concatenate(seq, axis=-1)
                batch = {"tokens": jnp.asarray(toks[..., :-1], jnp.int32),
                         "labels": jnp.asarray(toks[..., 1:], jnp.int32)}
                bs = batch_specs(batch, mesh, fed_axis="pod")
                batch = jax.device_put(batch, named(bs, mesh))
                key, sub = jax.random.split(key)
                params, vel, loss = jitted(params, vel, batch, jnp.int32(step), sub)
                if (step + 1) % 10 == 0:
                    print(f"  [{tag}] step {step+1:3d} "
                          f"loss={np.mean(np.asarray(loss)):.4f}")
        # Group divergence after gossip: should be small (aggregated).
        leaf = jax.tree_util.tree_leaves(params)[0]
        spread = float(jnp.max(jnp.std(leaf.astype(jnp.float32), axis=0)))
        print(f"  [{tag}] final loss={np.mean(np.asarray(loss)):.4f} inter-group param spread={spread:.5f}\n")


if __name__ == "__main__":
    main()
