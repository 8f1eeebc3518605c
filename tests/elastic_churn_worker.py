"""Worker body for the membership-churn chaos scenario.

Spawned by ``tools/chaos_run.py --scenario membership-churn`` and by
``tests/test_elastic.py`` against a sync-mode kvstore server with
eviction enabled.  Every live worker pushes the SAME constant gradient
(ones * CHURN_GRAD) each step, so a flushed merge round applies exactly
``num_workers * CHURN_GRAD`` to the weight no matter how many workers
contributed: full rounds sum it directly, shrunken rounds are
renormalized by ``num_workers / len(round)`` server-side.  The final
weight is therefore ``CHURN_TOTAL_STEPS * num_workers * CHURN_GRAD``
independent of kill/evict/join timing — the reproducibility invariant
the churn test asserts.

Env contract (beyond the usual DMLC_* worker vars):

* ``CHURN_TOTAL_STEPS``  — rounds the job must complete (default 10).
* ``CHURN_JOIN_STEP``    — step at which survivors gate until the
  mid-run joiner shows up in the membership table (default 6); the
  joiner starts its own loop at this step.
* ``CHURN_EXPECT_MEMBERS`` — live-set size both gates wait for (default
  3).  The launch-time workers also gate BEFORE step 0 until all of them
  are members: an elastic barrier counts the members there are, so a
  worker whose interpreter started late (a loaded host) would otherwise
  find its peers rounds ahead, push its own ``CHURN_TOTAL_STEPS`` rounds
  after them, and the weight would overshoot the invariant.
* ``CHURN_KILL_RANK`` / ``CHURN_FAULTS_SPEC`` / ``CHURN_FAULTS_SEED`` —
  the victim installs the seeded FaultPlan IN-PROCESS (only the matching
  rank, never a joiner): a plain ``MXNET_FAULTS_SPEC`` env would reach
  every worker with the same seed and kill them all.

Each worker prints one JSON line ``{rank, steps, final, target,
joiner}`` on success; the victim never gets there (the plan's ``kill``
is ``os._exit(137)``).
"""
import json
import os
import sys
import time


def main():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)

    import mxnet_tpu as mx
    from mxnet_tpu import faults, kvstore

    rank = int(os.environ["DMLC_WORKER_ID"])
    is_joiner = os.environ.get("MXNET_KVSTORE_ELASTIC_JOIN") == "1"
    n_total = int(os.environ.get("CHURN_TOTAL_STEPS", "10"))
    j_sync = int(os.environ.get("CHURN_JOIN_STEP", "6"))
    expect = int(os.environ.get("CHURN_EXPECT_MEMBERS", "3"))
    grad_c = float(os.environ.get("CHURN_GRAD", "1.0"))
    num_workers = int(os.environ.get("DMLC_NUM_WORKER", "1"))

    kill_rank = os.environ.get("CHURN_KILL_RANK")
    if kill_rank is not None and int(kill_rank) == rank and not is_joiner:
        faults.install(faults.FaultPlan(
            os.environ["CHURN_FAULTS_SPEC"],
            seed=int(os.environ.get("CHURN_FAULTS_SEED", "0"))))

    kv = kvstore.create("dist_async")
    kv.init("w", mx.nd.zeros((4,)))

    def wait_members(what):
        deadline = time.monotonic() + 60.0
        while len(kv.membership()["ranks"]) < expect:
            if time.monotonic() > deadline:
                print(json.dumps({"rank": rank, "error": what}), flush=True)
                sys.exit(4)
            time.sleep(0.05)

    if not is_joiner:
        # start gate: the rounds are counted from a full fleet
        wait_members("launch-time fleet never complete")
    target = float(n_total * num_workers) * grad_c
    grad = mx.nd.ones((4,)) * grad_c
    out = mx.nd.zeros((4,))
    steps = 0
    for it in range(j_sync if is_joiner else 0, n_total):
        # the victim's seeded plan kills here (before the push: its
        # contribution to this round must never be half-sent)
        faults.fire("churn.worker.step")
        if not is_joiner and it == j_sync:
            # grow gate: wait for the mid-run joiner so post-join rounds
            # demonstrably count the full live set
            wait_members("joiner never arrived")
        kv.push("w", grad)
        kv._barrier()
        steps += 1
    if is_joiner:
        # leave right away: the survivors' last round may still be
        # waiting on this member, and our departure is what flushes it
        kv.pull("w", out)
        final = float(out.asnumpy()[0])
    else:
        # rounds flush as stragglers leave; poll until the invariant
        # value lands (bounded, so a real stall still fails the test)
        deadline = time.monotonic() + 60.0
        while True:
            kv.pull("w", out)
            final = float(out.asnumpy()[0])
            if final >= target - 1e-6 or time.monotonic() > deadline:
                break
            time.sleep(0.05)
    print(json.dumps({"rank": rank, "steps": steps, "final": final,
                      "target": target, "joiner": is_joiner}), flush=True)
    kv.close()


if __name__ == "__main__":
    main()
