from .elastic import remesh_plan, reshard_tree
from .faults import (FaultEvent, FaultPlan, InjectedCrash, parse_fault_plan)
from .straggler import StragglerPolicy, best_finite_chain, rebalance_chains
from .supervisor import RunSupervisor, SupervisedResult

__all__ = ["remesh_plan", "reshard_tree", "StragglerPolicy",
           "best_finite_chain", "rebalance_chains", "FaultEvent",
           "FaultPlan", "InjectedCrash",
           "parse_fault_plan", "RunSupervisor", "SupervisedResult"]
