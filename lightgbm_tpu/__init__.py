"""LightGBM-TPU: a TPU-native gradient boosting framework.

A from-scratch re-design of the LightGBM v2 feature set for TPU hardware:
histogram construction and leaf-wise split search run as fused XLA/Pallas
programs over a `jax.sharding.Mesh`; the reference's socket/MPI collective
layer (src/network/) is replaced by XLA collectives (psum/all_gather)
inside shard_map.
"""

__version__ = "0.1.0"

import time as _time
# the set-up account's origin (obs/setup.py): before the first import
_T0_WALL, _T0_PERF = _time.time(), _time.perf_counter()

from .config import Config  # noqa: F401
from .io import BinnedDataset, BinMapper, Metadata  # noqa: F401
from .basic import Booster, Dataset  # noqa: F401
from .callback import (early_stopping, log_telemetry,  # noqa: F401
                       print_evaluation, record_evaluation, reset_parameter)
from . import obs  # noqa: F401
from . import serve  # noqa: F401
from .engine import CVBooster, cv, train, train_delta  # noqa: F401
from .sklearn import (LGBMClassifier, LGBMModel,  # noqa: F401
                      LGBMRanker, LGBMRegressor)
from .utils.log import LightGBMError  # noqa: F401

try:
    from .plotting import plot_importance, plot_metric, plot_tree  # noqa: F401
    _PLOTTING = ["plot_importance", "plot_metric", "plot_tree"]
except ImportError:  # matplotlib not installed
    _PLOTTING = []

obs.setup.open_account(_T0_WALL, _T0_PERF)
with obs.span("Setup::import", start=_T0_PERF):
    pass                    # everything above: jax's import, where this
                            # package is what imports jax first

__all__ = ["Dataset", "Booster", "Config",
           "train", "train_delta", "cv", "CVBooster",
           "LGBMModel", "LGBMRegressor", "LGBMClassifier", "LGBMRanker",
           "print_evaluation", "record_evaluation", "reset_parameter",
           "early_stopping", "log_telemetry", "obs", "serve",
           "LightGBMError"] + _PLOTTING
