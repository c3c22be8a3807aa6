"""What a family file hands a driver for training."""
import dataclasses
from typing import Any, Callable


def executor_seed(seed):
    """The driver's --seed folded to what the program can take.

    ``Variable.initial_value`` seeds numpy's RandomState with
    ``seed + crc32(name)``, which must stay under 2**32, and a traced
    PRNG key is built from an int32; the driver's seeds go past 2**31.
    So the program gets ``seed % 9973`` (a prime: seeds that differ by
    less than 9973 stay distinct)."""
    return int(seed) % 9973


@dataclasses.dataclass
class TrainSession:
    executor: Any
    feed_nodes: tuple
    make_batch: Callable        # (RandomState, batch) -> host arrays
    tokens_per_sequence: int
    # The executor's "validate" group is [loss, *outputs] in inference
    # mode; ``reference`` gives the same for the same weights and feed:
    # (params by name, feed values) -> (loss, [arrays]).
    reference: Callable
    loss_tolerance: float       # relative; reason in the reference file
    output_tolerance: float     # stats.row_errors; same

    def params_by_name(self):
        """Parameter arrays by checkpoint name. Valid only before the
        first training step: the step donates them."""
        return {node.name: arr for node, arr in
                self.executor.config.placeholder_to_arr_map.items()}
