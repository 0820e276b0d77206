"""``model.moe_group_kept_share``: the step's tokens whose kept groups include
the group of the experts held on this chip, over all its tokens of the routed
layers, in percent, from the counts the train step returns
(``TransformerTrainer.kept``): ``topk_group / n_group`` under
an even choice (50 at 4 of 8), and what the held share of the routes now
hangs on."""

from benchmarks.trace import linear

NAME = "model.moe_group_kept_share"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train_linear"}


def read(reading):
    return linear.group_kept_share(reading)
