"""Memory a chip holds while the step runs, by the COMPILER's count: the
``total_bytes`` of the account the fused step keeps of its own program
(``compiled.memory_analysis()``: arguments + outputs - aliases +
temporaries + generated code), in GB. ``peak_hbm`` beside it is the
runtime's counter, which leaves a running program's temporaries out. The
largest where the process staged more than one step; a program without
the account, or a backend that counts nothing, reports nothing."""

from benchlib import phases


def read(run):
    totals = [acc.memory["total_bytes"]
              for acc in phases.programs().values() if acc.memory]
    return max(totals) / 1e9 if totals else None
