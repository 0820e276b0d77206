"""SGD updater — reference ``updater/sgd_updater.h`` (SURVEY.md §2.16)."""

from __future__ import annotations

from .base import AddOption, Updater, register_updater


@register_updater
class SGDUpdater(Updater):
    """w -= lr * g (delta is a gradient)."""

    name = "sgd"
    num_slots = 0

    def apply_dense(self, w, state, delta, opt: AddOption):
        return w - opt.learning_rate * delta, state

    def row_increment(self, delta, opt: AddOption):
        return -opt.learning_rate * delta
