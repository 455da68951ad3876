"""The paper's contribution as a first-class feature: GPU/TPU-level
bottleneck analysis (HLO census + roofline), the Batching Configuration
Advisor (Eq. 2), and the replication planner + co-location simulator."""
from repro.core.hardware import (Hardware, TPU_V5E, H100_PAPER, HARDWARE,  # noqa
                                 hardware_for, local_hardware)
from repro.core.analysis import HloCensus, OpCensus, census_from_compiled, memory_from_compiled  # noqa
from repro.core.roofline import RooflineReport, roofline_report, model_flops_for  # noqa
from repro.core.perfmodel import (HostOverhead, decode_step_terms,  # noqa
                                  prefill_step_terms, decode_curves,
                                  max_batch_for, ServingCurves)
from repro.core.bca import BatchingConfigurationAdvisor, BCAResult, chunk_budget_for, slo_from_reference, knee_point, with_prefix_reuse, SpecPlan, speculation_advisor  # noqa
from repro.core.replication import ReplicationPlanner, ReplicationPlan, slice_mesh  # noqa
from repro.core.simulator import simulate_decode, replication_sweep, SimResult  # noqa
