"""GAME model classes.

Reference: photon-ml .../model/GAMEModel.scala:93-95 (Map[coordinateName ->
DatumScoringModel], score = sum of submodel scores), FixedEffectModel.scala
:29-104 (Broadcast[GLM] + featureShardId), RandomEffectModel.scala:126-168
(RDD[(entityId, GLM)] scored via join), RandomEffectModelInProjectedSpace
.scala, MatrixFactorizationModel.scala:141-178 (double-cogroup latent
scoring), DatumScoringModel.scala.

TPU-native: every model scores a GameDataset into a row-aligned [n] array;
the RDD-of-models becomes a dense [E, D] coefficient bank; the MF cogroup
becomes two row gathers + a dot, a chunk of rows at a time.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.game.data import GameDataset
from photon_ml_tpu.game.random_effect import score_random_effect
from photon_ml_tpu.game.random_effect_data import RandomEffectDataset
from photon_ml_tpu.models.glm import GeneralizedLinearModel
from photon_ml_tpu.task import TaskType

Array = jnp.ndarray


class DatumScoringModel:
    """score(dataset) -> row-aligned [n] raw scores (no offsets)."""

    def score(self, dataset: GameDataset) -> Array:  # pragma: no cover
        raise NotImplementedError


@dataclass
class FixedEffectModel(DatumScoringModel):
    """Global GLM over one feature shard (FixedEffectModel.scala)."""

    model: GeneralizedLinearModel
    feature_shard_id: str

    def score(self, dataset: GameDataset) -> Array:
        batch = dataset.batch_for_shard(self.feature_shard_id)
        return self.model.score(batch)


@dataclass
class RandomEffectModel(DatumScoringModel):
    """Per-entity coefficient bank [E, D] over a local projection
    (RandomEffectModel + RandomEffectModelInProjectedSpace)."""

    bank: Array  # [E, D]
    re_dataset: RandomEffectDataset
    random_effect_type: str
    feature_shard_id: str
    # per-entity coefficient variances [E, D], populated when the problem
    # runs with compute_variances (isComputingVariance analog)
    variances: Optional[Array] = None

    def score(self, dataset: GameDataset) -> Array:
        # The bank's projection is tied to re_dataset; scoring another
        # dataset requires a re-projected view built by the data layer.
        return score_random_effect(self.bank, self.re_dataset)

    def score_rows(self, re_view: RandomEffectDataset) -> Array:
        return score_random_effect(self.bank, re_view)


@dataclass
class MatrixFactorizationModel(DatumScoringModel):
    """score_i = rowLatent[rowId_i] . colLatent[colId_i]
    (MatrixFactorizationModel.scala:141-178)."""

    row_effect_type: str
    col_effect_type: str
    row_latent: Array  # [R, K]
    col_latent: Array  # [C, K]

    @property
    def num_latent_factors(self) -> int:
        return self.row_latent.shape[1]

    def score(self, dataset: GameDataset) -> Array:
        rows, cols = mf_device_codes(
            dataset, self.row_effect_type, self.col_effect_type
        )
        return mf_score(
            self.row_latent, self.col_latent, rows, cols
        )[: dataset.num_rows]


# Rows one step of :func:`mf_score` takes the two factor rows of: the
# [chunk, K] pair is all that is ever written (64 MiB each at K = 64),
# never [n, K].
MF_SCORE_CHUNK = 1 << 18


def mf_device_codes(dataset: GameDataset, row_type: str, col_type: str):
    """The two code columns of ``dataset`` on the device, padded with -1
    to whole chunks of :data:`MF_SCORE_CHUNK`, ``[chunks, chunk]`` each;
    uploaded once a dataset and pair of effect types."""
    cache = dataset.__dict__.setdefault("_mf_device_codes", {})
    key = (row_type, col_type)
    if key not in cache:
        n = dataset.num_rows
        chunk = min(MF_SCORE_CHUNK, max(n, 1))
        pad = -n % chunk

        def put(codes):
            codes = np.asarray(codes, np.int32)
            return jnp.asarray(
                np.concatenate([codes, np.full(pad, -1, np.int32)])
                .reshape(-1, chunk)
            )

        cache[key] = (
            put(dataset.entity_codes[row_type]),
            put(dataset.entity_codes[col_type]),
        )
    return cache[key]


@jax.jit
def mf_score(row_latent, col_latent, rows, cols):
    """``score_i = rowLatent[rows_i] . colLatent[cols_i]`` as ONE named
    program (module ``mf_score``, scope ``cd.score``) over
    ``[chunks, chunk]`` codes, a chunk a scan step: the gathered factor
    rows of one chunk are the only [*, K] temporaries. A row without
    either entity (code -1) scores 0. Elementwise float32 products and
    sums: ``jax_default_matmul_precision`` does not reach it."""

    def chunk_scores(_, codes):
        r, c = codes
        p = jnp.take(row_latent, jnp.maximum(r, 0), axis=0)
        q = jnp.take(col_latent, jnp.maximum(c, 0), axis=0)
        score = jnp.sum(p * q, axis=-1)
        return None, jnp.where((r >= 0) & (c >= 0), score, 0.0)

    with jax.named_scope("cd.score"):
        _, out = jax.lax.scan(chunk_scores, None, (rows, cols))
        return out.reshape(-1)


@dataclass
class GameModel:
    """Ordered coordinate name -> submodel; total score = sum
    (GAMEModel.scala:93-95)."""

    models: Dict[str, DatumScoringModel] = field(default_factory=dict)
    task: TaskType = TaskType.LOGISTIC_REGRESSION

    def get_model(self, name: str) -> Optional[DatumScoringModel]:
        return self.models.get(name)

    def update_model(self, name: str, model: DatumScoringModel) -> "GameModel":
        new = dict(self.models)
        new[name] = model
        return GameModel(new, self.task)

    def score(self, dataset: GameDataset) -> Array:
        total = jnp.zeros((dataset.num_rows,), jnp.float32)
        for m in self.models.values():
            total = total + m.score(dataset)
        return total
