"""LightGBM-TPU: a TPU-native gradient boosting framework.

A brand-new JAX/XLA/Pallas implementation of the LightGBM feature set
(histogram-based leaf-wise GBDT with GOSS/EFB, the full objective/metric zoo,
DART/RF boosting, distributed training over a TPU mesh) — designed TPU-first,
not ported. See SURVEY.md at the repo root for the blueprint.

Public API mirrors the reference python-package:

    import lightgbm_tpu as lgb
    train_set = lgb.Dataset(X, label=y)
    booster = lgb.train({"objective": "binary"}, train_set, num_boost_round=100)
    preds = booster.predict(X_test)
"""

__version__ = "0.1.0"

import sys as _sys
import time as _time

# the package_import record's clock reads (obs.record_package_import)
_marks = []  # graftlint: disable=module-mutable-state -- filled by _mark() while the package imports, deleted below


def _mark(group):
    _marks.append((group, _time.perf_counter()))  # graftlint: disable=naked-timer -- read before obs is imported; times HOST imports


_jax_preimported = "jax" in _sys.modules
_mark("entry")

from .config import Config
from .utils.log import Log, LightGBMError
from . import obs

try:  # full API surface; modules come online as the build proceeds
    from .basic import Booster, Dataset, register_logger
    from .engine import train, cv, CVBooster
    _mark("core")
    from . import serve  # noqa: F401 — lgb.serve.PredictSession et al.
    from . import online  # noqa: F401 — lgb.online.OnlineTrainer et al.
    _mark("serve_online")
    from .plotting import (  # noqa: F401
        create_tree_digraph,
        plot_importance,
        plot_metric,
        plot_tree,
    )
    _mark("plotting")
    from .callback import (
        early_stopping,
        log_evaluation,
        print_evaluation,
        record_evaluation,
        reset_parameter,
        EarlyStopException,
    )
except ImportError:  # pragma: no cover — bootstrap only
    pass
_mark("core")

try:  # sklearn wrappers are optional (sklearn itself may be absent)
    from .sklearn import LGBMModel, LGBMClassifier, LGBMRegressor, LGBMRanker
    _SKLEARN = ["LGBMModel", "LGBMClassifier", "LGBMRegressor", "LGBMRanker"]
except ImportError:  # pragma: no cover
    _SKLEARN = []
_mark("sklearn")
obs.record_package_import(_marks, _jax_preimported)
del _marks

__all__ = [
    "Config",
    "obs",
    "Log",
    "LightGBMError",
    "Dataset",
    "Booster",
    "register_logger",
    "train",
    "cv",
    "CVBooster",
    "early_stopping",
    "log_evaluation",
    "print_evaluation",
    "record_evaluation",
    "reset_parameter",
    "EarlyStopException",
    "plot_importance",
    "plot_metric",
    "plot_tree",
    "create_tree_digraph",
] + _SKLEARN
