"""``model.moe_held_route_share``: routes that reached experts held on this chip
over all routes of the routed layers, in percent, from the counts the train
step returns (``TransformerTrainer.routes``): ``held / of`` under even
routing (6.25 at 16 of 256), and how far Zipf ids and the router move it."""

from benchmarks.trace import kinds

NAME = "model.moe_held_route_share"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {"model": {"experts_held": True}}


def read(reading):
    return kinds.held_route_share(reading)
