"""GAME (Generalized Additive Mixed Effects): multi-shard data, per-entity
random effects, block coordinate descent. See module docstrings for
reference citations."""

from photon_ml_tpu.game.config import (
    FactoredRandomEffectConfiguration,
    FeatureShardConfiguration,
    FixedEffectDataConfiguration,
    MatrixFactorizationConfiguration,
    ProjectorType,
    RandomEffectDataConfiguration,
)
from photon_ml_tpu.game.coordinate import (
    Coordinate,
    FactoredRandomEffectCoordinate,
    FixedEffectCoordinate,
    MatrixFactorizationCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.game.coordinate_descent import (
    CoordinateDescent,
    CoordinateDescentResult,
)
from photon_ml_tpu.game.data import (
    EntityIndex,
    GameDataset,
    build_game_dataset,
    build_game_dataset_from_files,
)
from photon_ml_tpu.game.coordinate import PodRandomEffectCoordinate
from photon_ml_tpu.game.model import (
    DatumScoringModel,
    FixedEffectModel,
    GameModel,
    MatrixFactorizationModel,
    RandomEffectModel,
)
from photon_ml_tpu.game.pod import (
    EntityShardSpec,
    PodRandomEffectModel,
    PodRandomEffectProblem,
    ShardedREBank,
)
from photon_ml_tpu.game.random_effect import (
    RandomEffectOptimizationProblem,
    RandomEffectTracker,
    score_random_effect,
)
from photon_ml_tpu.game.random_effect_data import (
    RandomEffectBucket,
    RandomEffectDataset,
    build_random_effect_dataset,
)

__all__ = [
    "FactoredRandomEffectConfiguration",
    "FeatureShardConfiguration",
    "FixedEffectDataConfiguration",
    "MatrixFactorizationConfiguration",
    "ProjectorType",
    "RandomEffectDataConfiguration",
    "Coordinate",
    "FactoredRandomEffectCoordinate",
    "FixedEffectCoordinate",
    "MatrixFactorizationCoordinate",
    "RandomEffectCoordinate",
    "PodRandomEffectCoordinate",
    "EntityShardSpec",
    "PodRandomEffectModel",
    "PodRandomEffectProblem",
    "ShardedREBank",
    "CoordinateDescent",
    "CoordinateDescentResult",
    "EntityIndex",
    "GameDataset",
    "build_game_dataset",
    "build_game_dataset_from_files",
    "DatumScoringModel",
    "FixedEffectModel",
    "GameModel",
    "MatrixFactorizationModel",
    "RandomEffectModel",
    "RandomEffectOptimizationProblem",
    "RandomEffectTracker",
    "score_random_effect",
    "RandomEffectBucket",
    "RandomEffectDataset",
    "build_random_effect_dataset",
]
