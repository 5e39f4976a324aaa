from repro.core.task import Task
from repro.core.dp import DepthPlanner, brute_force_plan, task_options
from repro.core.greedy import greedy_update
from repro.core.utility import (ExpIncrease, LinIncrease, MaxIncrease, Oracle,
                                make_predictor)
from repro.core.schedulers import (EDF, LCF, RR, Policy, RTDeepIoT,
                                   WeightedRTDeepIoT)
from repro.core.simulator import SimResult, Workload

__all__ = ["Task", "DepthPlanner", "brute_force_plan", "task_options",
           "greedy_update", "ExpIncrease", "LinIncrease", "MaxIncrease",
           "Oracle", "make_predictor", "EDF", "LCF", "RR", "Policy",
           "RTDeepIoT", "WeightedRTDeepIoT", "SimResult", "Workload"]
