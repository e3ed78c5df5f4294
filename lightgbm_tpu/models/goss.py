"""GOSS: Gradient-based One-Side Sampling.

Reference: src/boosting/goss.hpp.  Keep all rows with |g*h| in the top
``top_rate`` fraction; randomly keep ``other_rate`` of the rest with
gradient amplification x (1-a)/b (goss.hpp:79-124); no sampling during the
first 1/learning_rate iterations (goss.hpp:129); bagging combination is
forbidden (checked at config time).

The draw is a stage of the fused round (models/gbdt.py ``_round_step``,
ops/goss.py ``sample``): the exact top set by the ``goss_sample`` kernel,
the rest by a stateless function of (``bagging_seed``, iteration, row), so
unsampled and sampled rounds run one compiled ``train_step`` and a
snapshot needs no generator state.  The grower then compacts the kept
rows by the partition kernel and routes the others through the tree
(ops/ordered_grow.py).  Amplification is applied to gradients AND
hessians (like the reference, goss.hpp:108-118) while the 0/1 row mask
keeps leaf counts meaning true row counts.
"""

from __future__ import annotations

from .. import obs
from ..ops.goss import GossParams
from ..utils import log
from .gbdt import GBDT


class GOSS(GBDT):
    submodel_name = "goss"

    def __init__(self, config, train_set, objective=None):
        super().__init__(config, train_set, objective)
        self._goss = GossParams(
            float(config.top_rate), float(config.other_rate),
            over_devices=getattr(self, "_mesh", None) is not None)
        if self._goss.top_rate + self._goss.other_rate >= 1.0:
            log.warning("top_rate + other_rate >= 1.0 in GOSS: no sampling")
        if train_set is not None:
            obs.set_gauge("goss_top_rows",
                          int(self._goss.top_rate * self.num_data))
            obs.set_gauge("goss_other_rows",
                          int(self._goss.other_rate * self.num_data))
