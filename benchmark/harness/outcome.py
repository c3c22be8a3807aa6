"""What a driver hands back to ``run.py``."""
import dataclasses


@dataclasses.dataclass
class Options:
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    trace_dir: str          # where a traced run writes its profile
    log: object             # log(dict): one JSON line, before the last
    process_start: float    # time.perf_counter() value at process start
    devices: list
    compiles: object        # harness.compiles.CompileCounter


@dataclasses.dataclass
class Outcome:
    correct: bool
    attempted: int
    failed: int
    setup_s: float
    end_to_end: dict        # metric name -> value, as measured
    facts: dict             # what the per-layer readers may read
    traced: bool = False    # a profile was written to Options.trace_dir
    committed_bytes: int = 0    # device.committed_bytes at window end
