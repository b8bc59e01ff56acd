from repro.distributed.fault_tolerance import (  # noqa: F401
    HeartbeatMonitor, NodeFailure, run_with_recovery)
