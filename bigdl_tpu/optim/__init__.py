"""bigdl_tpu.optim — training/inference runtime (reference: optim/, SURVEY.md §2.6)."""

from bigdl_tpu.optim.optim_method import (
    OptimMethod, SGD, Adam, AdamW, Adagrad, Adadelta, Adamax, RMSprop, Ftrl,
    LBFGS, ParallelAdam, TrainState,
    LearningRateSchedule, Default, Poly, Step, MultiStep, EpochStep, EpochDecay,
    Exponential, Plateau, Warmup, SequentialSchedule, EpochSchedule, NaturalExp,
    CosineDecay,
)
from bigdl_tpu.optim.ema import EMA
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.optim.validation import (
    ValidationMethod, ValidationResult, AccuracyResult, LossResult,
    Top1Accuracy, Top5Accuracy, Loss, MAE, TreeNNAccuracy,
    BinaryAccuracy, AUC,
)
from bigdl_tpu.optim.regularizer import (
    Regularizer, L1Regularizer, L2Regularizer, L1L2Regularizer,
)
from bigdl_tpu.optim.optimizer import Optimizer, LocalOptimizer, make_train_step
from bigdl_tpu.optim.evaluator import Evaluator
from bigdl_tpu.optim.generation_service import GenerationService
from bigdl_tpu.optim.predictor import LocalPredictor, PredictionService
from bigdl_tpu.optim.metrics import Metrics
