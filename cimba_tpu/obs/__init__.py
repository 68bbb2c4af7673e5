"""Observability: flight recorder, metrics registry, exporters, profiling.

The reference makes its event loop inspectable with ``cmb_logger``
flag-mask lines and ``cmb_event_queue_print`` golden dumps; this package
is the TPU-native half of that story — observability that lives *inside*
the compiled program as arrays, because a host callback cannot cross an
XLA while-loop iteration (let alone a Mosaic kernel) without serializing
the run it is meant to observe.

Three parts, all trace-time gated like :mod:`cimba_tpu.utils.logger`
(disabled = literally zero ops in the jaxpr):

* :mod:`~cimba_tpu.obs.trace` — the **flight recorder**: a
  capacity-bounded on-device ring buffer ``(t, pid, kind, arg, seq)``
  written at the dispatch site in ``core/loop.py``.  One ring per
  replication under ``vmap``.
* :mod:`~cimba_tpu.obs.metrics` — the **metrics registry**: named
  counters/gauges/histograms carried as Sim arrays (dispatches by kind,
  queue high-water marks, guard retries, chain-length histogram),
  pooled across replications and over ICI.
* :mod:`~cimba_tpu.obs.export` / :mod:`~cimba_tpu.obs.prof` —
  **exporters and profiling**: Chrome-trace/Perfetto JSON of a
  replication's ring, and a :class:`~cimba_tpu.obs.prof.RunReport`
  capturing the compile-vs-execute wall-time split, device memory and a
  metrics snapshot.

Kernel-path contract (docs/07): both the recorder and the metrics
registry raise a loud build-time error when an enabled instance is
traced under ``config.KERNEL_MODE`` — mirroring ``logger._emit``.

Host-side: :mod:`~cimba_tpu.obs.telemetry` (the serving control-plane's
time-series registry, request spans, health sampler — stdlib-only) and
:mod:`~cimba_tpu.obs.expose` (``/metrics`` Prometheus text, ``/healthz``,
``/varz`` over HTTP).  Opt-in with the same discipline: everything takes
``telemetry=None`` and a None means no threads, no request span trees,
and compiled programs bitwise-unchanged (docs/17_telemetry.md).  Always
on beside it: the program spans of the Service's wave loop and the
stream runner, and the compile counter, in one bounded ring
(``telemetry.span``, ``telemetry.program_spans``).

Provenance: :mod:`~cimba_tpu.obs.audit` — the determinism-audit plane
(docs/18_audit.md): chunk-boundary carry digests (trace-time gated,
``audit=False`` == jaxpr-identical), content-addressed run cards, and
divergence localization (``tools/audit_diff.py``).
"""

from cimba_tpu.obs import metrics, telemetry, trace  # noqa: F401

# the process's one compile counter (docs/17_telemetry.md), from the
# first import on
telemetry.count_compiles()

# export, prof and expose are imported lazily by callers (they pull in
# numpy/json/http and the runner surface; the hot loop only ever needs
# trace/metrics, and the Service and runner the program spans)
