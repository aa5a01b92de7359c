"""How often the engine's loop ran a decode step ahead: of the decode
programs it enqueued between the runner's two snapshots, the share
enqueued while their predecessor was still unharvested
(``metrics_snapshot()["engine"]["run_ahead"]``: ``ahead`` over
``ahead + drained``, a program enqueued on an empty pipe being
``drained``). None where the program keeps no such counter, or
enqueued nothing in the window."""

LAYER = 'engine (serving/generation/engine.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'program_counter'
MOVES = 'serve_tokens_per_s'


def read(run):
    before = run["snap0"].get("engine", {}).get("run_ahead")
    after = run["snap1"].get("engine", {}).get("run_ahead")
    if before is None or after is None:
        return None
    ahead = after["ahead"] - before["ahead"]
    enqueued = ahead + after["drained"] - before["drained"]
    return 100.0 * ahead / enqueued if enqueued > 0 else None
