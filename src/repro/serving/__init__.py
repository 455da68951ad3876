from repro.serving.engine import (ContinuousBatchingEngine, EngineConfig,  # noqa
                                  RequestTooLarge, StepFunctions)
from repro.serving.workload import (FINISH_ABORT, FINISH_DEADLINE,  # noqa
                                    FINISH_FAILED, FINISH_LENGTH,
                                    FINISH_REASONS, FINISH_SHED, FINISH_STOP,
                                    Request, RequestState, SamplingParams,
                                    arrival_times, long_short_workload,
                                    repetitive_workload,
                                    shared_prefix_workload, sharegpt_like)
from repro.serving.faults import (FAULT_KINDS, FaultInjector, FaultSpec,  # noqa
                                  InjectedFault, parse_fault)
from repro.serving.metrics import (Percentiles, ServingMetrics,  # noqa
                                   collect_from_engine)
from repro.serving.cluster import (ClusterMetrics, ReplicatedCluster,  # noqa
                                   autoscale)
from repro.serving.scheduler import Scheduler, StepPlan  # noqa
from repro.serving.spec import Drafter, PromptLookupDrafter  # noqa
from repro.serving.executor import Executor  # noqa
from repro.serving.api import (AsyncRequestHandle, AsyncServingAPI,  # noqa
                               GenerationOutput, RequestHandle,
                               ServingAPI)
from repro.serving.obs import (BoundedSeries, Dashboard,  # noqa
                               MemoryGapAuditor,
                               MetricsEmitter, Observability, SLO,
                               SLOMonitor, Tracer,
                               WindowAggregator, default_slos,
                               lint_prometheus, metrics_from_json,
                               metrics_to_json, prometheus_text,
                               validate_chrome_trace)
