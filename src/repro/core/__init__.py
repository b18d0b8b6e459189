from .assignment import greedy_assign, hungarian, lpt_order
from .budget import admission_mask, max_tokens_clamp
from .dispatchers import DISPATCHERS, RandomDispatch, RoundRobin, \
    ShortestQueue
from .driver import make_requests, run_cell
from .engine import (AssignmentResult, BatchView, EngineConfig,
                     SchedulingPolicy, ServingEngine)
from .hotpath import FusedHotPath
from .pipeline import PipelineConfig, PipelineScheduler
from .policies import (POLICIES, RouterDispatchPolicy, fit_policy,
                       make_policy, register_policy, train_data)
from .routers import AvengersProRouter, BestRouteRouter, PassthroughRouter
from .scheduler import EstimatorBundle, RBConfig, RouteBalance, \
    RouteBalancePolicy
from .scoring import score_matrix, score_row
from .weights import PRESETS, sweep, validate
