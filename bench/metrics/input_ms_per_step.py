"""The input feed's host time per step: taking the next batch and the
program's ``device_put`` of it (the harness's own spans)."""


def read(run):
    n = max(len(run.spans["next_batch"]), 1)
    return 1e3 * (sum(run.spans["next_batch"])
                  + sum(run.spans["device_put"])) / n
