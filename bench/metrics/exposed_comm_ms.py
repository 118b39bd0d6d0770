"""exposed_comm_ms (ms/round): device time per round, on the chip the
readers read, in which a collective op of the round program runs and no
other op does (core/shard.py: the model broadcast's all-gather, the MAC
superposition and slice hand-off of the partial and clean sums, the
round metrics' psums). The sharded round names none of its stages, so
its collectives are found by their HLO opcode (bench/trace.py
``is_collective``)."""


def read(view):
    return view.collective_ms(exposed=True)
