"""batch.dead_lane_share: 100 * (1 - committed member-iterations /
lane-steps) over the window.  A lane-step is one lane of a vmapped chunk
program taking one step of its loop (``gp.GPScan.lane_steps``, summed over
the family solves of every request); a lane that takes a step without
committing an iteration is a member that has already latched but runs on
under ``vmap`` until the last member of its bucket latches, or a padding
lane of a bucket.  Nothing where the program reports no lane-steps."""


def read(run):
    lanes = sum(r.get("lane_steps") or 0 for r in run.records)
    return 100.0 * (1.0 - run.iterations / lanes) if lanes else None
