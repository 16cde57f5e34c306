"""Quickstart: one round of the paper's pipeline, end to end, on CPU.

  1. draw a wireless channel realization for 5 UEs,
  2. solve the communication-learning trade-off (Algorithm 1) for the
     pruning rates rho_i and bandwidth allocation B_i,
  3. run one pruned-FedSGD round with packet-error-aware aggregation,
  4. evaluate the Theorem-1 convergence bound for the realized rates.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation, pruning, tradeoff, wireless
from repro.core.convergence import ConvergenceBound, SmoothnessParams
from repro.data import synthetic
from repro.launch.compile_cache import enable_compile_cache
from repro.models import mlp

enable_compile_cache()

I = 5                                  # UEs (paper Table I)
SAMPLES = np.array([30, 40, 50, 30, 40], np.float64)

# --- 1. wireless channel ----------------------------------------------------
cfg = wireless.WirelessConfig()        # Table I defaults
channel = wireless.Channel(I, seed=0)
h_up, h_down = channel.sample_gains()
print("uplink gains:", np.array2string(h_up, precision=2))

# --- 2. trade-off optimization (Algorithm 1) --------------------------------
bound = ConvergenceBound(SmoothnessParams(), SAMPLES)
prob = tradeoff.TradeoffProblem(
    cfg=cfg, bound=bound, h_up=h_up, h_down=h_down,
    tx_power=np.full(I, cfg.tx_power_ue_w), cpu_hz=np.full(I, 5e9),
    num_samples=SAMPLES, max_prune=np.full(I, 0.7))
sol = tradeoff.solve_alternating(prob)
print(f"\nAlgorithm 1 converged in {sol.iterations} iterations")
print("pruning rates rho*:", np.round(sol.prune, 3))
print("bandwidth B* (MHz):", np.round(sol.bandwidth / 1e6, 3),
      f"(sum {sol.bandwidth.sum()/1e6:.2f} <= {cfg.bandwidth_hz/1e6:.0f})")
print("packet error rates:", np.round(sol.per, 4))
print(f"round deadline t~*: {sol.deadline*1e3:.1f} ms   "
      f"total cost: {sol.total_cost:.4f}")

# --- 3. one pruned-FedSGD round ----------------------------------------------
data = synthetic.make_dataset(seed=0)
parts = synthetic.partition_iid([int(k) for k in SAMPLES], data, seed=0)
params = mlp.init_mlp_classifier(jax.random.PRNGKey(0), data.dim,
                                 mlp.SHALLOW_HIDDEN, data.num_classes)

grads, losses = [], []
for i, idx in enumerate(parts):
    masks = pruning.magnitude_masks(params, float(sol.prune[i]))
    pruned = pruning.apply_masks(params, masks)
    x = jnp.asarray(data.x_train[idx])
    y = jnp.asarray(data.y_train[idx])
    loss, g = jax.value_and_grad(mlp.classifier_loss)(pruned, x, y)
    losses.append(float(loss))
    grads.append(pruning.apply_masks(g, masks))   # pruned coords upload 0

stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *grads)
arrivals = aggregation.sample_arrivals(jax.random.PRNGKey(1),
                                       jnp.asarray(sol.per))
print("\npacket arrivals C_i:", np.asarray(arrivals, int))
g_global = aggregation.aggregate(stacked, jnp.asarray(SAMPLES, jnp.float32),
                                 arrivals)
params = jax.tree.map(lambda p, g: p - 1e-3 * g, params, g_global)
print("mean local loss:", float(np.mean(losses)))

# --- 4. Theorem-1 bound for the realized round --------------------------------
print(f"\nTheorem 1 bound after S=200 rounds at these rates: "
      f"{bound.bound(200, sol.per, sol.prune):.3f}")
print(f"  initial term : {bound.initial_term(200):.4f}")
print(f"  packet error : {bound.packet_error_term(sol.per):.4f}")
print(f"  pruning      : {bound.pruning_term(sol.prune):.4f}")
