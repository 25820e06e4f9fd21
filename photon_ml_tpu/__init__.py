"""photon-ml-tpu: a TPU-native (JAX/XLA/pjit) framework with the capabilities
of LinkedIn's Photon ML (large-scale GLM + GAME/GLMix training).

The compute/communication layer is JAX on TPU instead of Spark RDDs:

- sparse example batches are statically-shaped, device-sharded arrays
  (``photon_ml_tpu.data.batch``),
- the map-reduce gradient/Hessian "aggregators" of the reference
  (reference: photon-ml .../function/ValueAndGradientAggregator.scala) are
  fused jit kernels reduced with ``jax.lax.psum`` over the mesh
  (``photon_ml_tpu.ops.objective``, ``photon_ml_tpu.parallel``),
- LBFGS/OWLQN/TRON are ``lax.while_loop`` programs, vmap-able for the
  per-entity random-effect solves (``photon_ml_tpu.optim``),
- GAME coordinate descent keeps residual scores device-resident
  (``photon_ml_tpu.game``).
"""

from photon_ml_tpu.task import TaskType

__version__ = "0.1.0"
__all__ = ["TaskType", "__version__"]
