from .dropless import DroplessMoE, Routing, route_topk
from .experts import ExpertMLP, GatedExpertMLP, ReluSquaredExpertMLP
from .layer import MoE
from .sharded_moe import (MOELayer, RoutingStats, TopKGate,
                          collect_routing_stats, emit_routing_stats,
                          sum_routing_stats, top1gating, top2gating)
