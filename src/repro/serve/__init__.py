"""Serving layer: single-DB service and multi-tenant control plane.

:class:`ProfilingService` (:mod:`repro.serve.profiler_service`) is the
data plane — many concurrent requests over one RefDB, bit-exact with
sequential runs — on top of the generic :class:`FixedShapeScheduler`
(:mod:`repro.serve.scheduler`).  Above it, :class:`RefDBRegistry`
(:mod:`repro.serve.registry`) owns named databases with versioned,
delta-updatable snapshots, and :class:`TenantRouter`
(:mod:`repro.serve.router`) maps tenants to databases with per-tenant
quotas and zero-downtime hot-swap.  :mod:`repro.serve.fleet` replicates
that whole stack across simulated hosts behind one
:class:`FleetController` — pull-based version replication, load-aware
tenant routing with mid-flight failover, and fleet-coordinated
two-phase hot-swaps.
"""

from repro.serve.scheduler import Cohort, FixedShapeScheduler, pow2_buckets
from repro.serve.profiler_service import (ProfileHandle, ProfileRequest,
                                          ProfilingService, RequestState,
                                          ServiceOverloaded)
from repro.serve.registry import RefDBRegistry, RefDBSnapshot
from repro.serve.router import (RoutedHandle, RouterClosed, TenantRouter,
                                TenantSpec)
from repro.serve.fleet import (FleetController, FleetHandle, HostDown,
                               HostReplica, HostState, NoHealthyHosts)

__all__ = [
    "Cohort", "FixedShapeScheduler", "pow2_buckets",
    "ProfileHandle", "ProfileRequest", "ProfilingService", "RequestState",
    "ServiceOverloaded",
    "RefDBRegistry", "RefDBSnapshot",
    "RoutedHandle", "RouterClosed", "TenantRouter", "TenantSpec",
    "FleetController", "FleetHandle", "HostDown", "HostReplica",
    "HostState", "NoHealthyHosts",
]
