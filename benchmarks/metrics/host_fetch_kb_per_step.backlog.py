"""What ``ProgramRunner.run`` brought from the device to the host, a
decode iteration: the growth of ``metrics_snapshot()["engine"]
["fetch_bytes"]`` (the ``nbytes`` of every array traffic's dispatches
fetched, prefills' among them) between the runner's two snapshots,
over the decode iterations (``batch_occupancy.steps``), in KB of 1,000
bytes. None where the program keeps no such counter."""
from benchmarks import readers

LAYER = 'decoder (serving/generation/model_fns.py)'
UNIT = 'KB'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'serve_tokens_per_s'


def read(run):
    before = run["snap0"].get("engine", {}).get("fetch_bytes")
    after = run["snap1"].get("engine", {}).get("fetch_bytes")
    steps = readers.decode_steps(run)
    if before is None or after is None or steps <= 0:
        return None
    return (after - before) / steps / 1e3
