"""Serving config block.

Reference role: DeepSpeed-MII's deployment/``RaggedInferenceEngineConfig``
knobs for the persistent server (queue sizing, response behavior under load);
validated pydantic-style like the other config blocks (``config_v2.py``,
``telemetry/config.py``).
"""

from typing import Dict, Literal, Optional, Tuple

from pydantic import Field, field_validator, model_validator

from deepspeed_tpu.runtime.config_utils import DeepSpeedConfigModel

DEFAULT_MAX_RESUME_BODY_BYTES = 2 << 30
"""One authority for the ``/v1/resume`` body bound — shared by
``ServingConfig``, ``FleetConfig`` and ``serving/server.py`` so the router
and a replica can never disagree on whether the same payload is admissible."""


class PrefixCacheConfig(DeepSpeedConfigModel):
    """Automatic prefix caching (radix-tree KV reuse with copy-on-write block
    sharing — ``inference/v2/ragged/prefix_cache.py``). Off by default: the
    trie pins finished sequences' prefix blocks, so a cache-enabled scheduler
    intentionally does NOT return the KV pool to empty between requests."""

    enabled: bool = False
    """Look up every admitted prompt's longest cached prefix and publish
    completed sequences' full blocks back to the trie."""

    max_blocks: Optional[int] = Field(None, ge=1)
    """Cap on device blocks the trie may pin; None = bounded only by the pool
    (the KV-pressure path evicts unreferenced trie leaves LRU-first before
    touching live sequences)."""

    min_prefix_blocks: int = Field(1, ge=1)
    """Smallest cached-prefix match (in blocks) worth applying to a request;
    shorter matches prefill cold."""

    digest_catalog_limit: int = Field(64, ge=0)
    """How many trie-node digests (truncated hex, recency-first) the replica
    publishes in its probe doc for the fleet's cache-aware routing; 0 turns
    publication off (the replica then only receives hash-routed traffic)."""


class SpeculativeConfig(DeepSpeedConfigModel):
    """Speculative decoding (``inference/v2/spec/``): a drafter proposes
    continuation tokens per sequence per decode step at batch-build time and
    the engine verifies every proposed position in ONE ragged forward.
    Output is token-identical to non-speculative decoding at the same seed —
    greedy and sampled — the only effect is fewer decode dispatches. Off by
    default.

    Two drafter families, selected by ``drafter``: ``prompt_lookup`` mines
    n-gram repeats (linear ``1+k`` feeds; wins on repetitive text, k adapts
    to 0 elsewhere) and ``learned`` reads the target's hidden state through
    trained Medusa-style heads and proposes a branching token TREE (wins on
    arbitrary text after self-distillation — ``bin/dstpu_spec_train``).
    ``auto`` arbitrates per request on measured per-drafter acceptance
    EWMAs, probing the loser periodically. Every draft is a ``TokenTree``
    verified by ``engine.verify_tree``, which picks its program from the
    trees' shape (chains: the causal feed ``put`` takes; a branching tree:
    the tree-attention mask), never from ``drafter``."""

    def __init__(self, strict=False, **data):
        # the base model drops "auto"-valued kwargs so defaults apply (the
        # reference's use-the-default marker) — but "auto" is a REAL drafter
        # mode here; route it around the filter and through validation
        drafter = data.pop("drafter", None)
        super().__init__(strict=strict, **data)
        if drafter is not None:
            self.drafter = drafter

    enabled: bool = False
    """Draft at batch-build time and run multi-token verify feeds through
    the decode path."""

    drafter: Literal["prompt_lookup", "learned", "auto"] = "prompt_lookup"
    """Who drafts: ``prompt_lookup`` (chains), ``learned`` (loads the draft
    heads; branching trees) or ``auto`` (both, raced per request)."""

    max_draft_tokens: int = Field(4, ge=1)
    """Upper bound on draft tokens per sequence per step (k). The effective k
    adapts per request from a measured acceptance EWMA and reaches 0 on
    adversarial (pattern-free) text. For the learned drafter this caps tree
    DEPTH (bounded additionally by ``num_draft_heads``)."""

    num_draft_heads: int = Field(3, ge=1, le=8)
    """Medusa heads a freshly-initialized learned drafter carries (head ``h``
    predicts the token ``h + 2`` positions past the hidden state); ignored
    when ``draft_head_path`` loads trained heads with their own count."""

    tree_width: int = Field(2, ge=1)
    """Candidate tokens per head the learned drafter may branch over when
    growing the draft tree (best-first by joint log-probability)."""

    tree_node_budget: int = Field(8, ge=2)
    """Cap on nodes per draft tree (root included), a prompt-lookup chain
    too. Tree nodes are fed tokens: they compete under the ragged token
    budget and ``draft_token_budget``."""

    draft_head_path: Optional[str] = None
    """Trained draft-head ``.npz`` (``bin/dstpu_spec_train`` output) for the
    learned drafter; None = fresh deterministic heads (acceptance adapts k
    to 0 until they are trained, so this is safe but slow)."""

    min_ngram: int = Field(1, ge=1)
    max_ngram: int = Field(3, ge=1)
    """Self-lookup n-gram window: the drafter matches the longest history
    suffix between these bounds against earlier occurrences."""

    accept_alpha: float = Field(0.5, gt=0, le=1)
    """EWMA smoothing for the per-request acceptance rate that drives the
    adaptive k (higher = faster back-off AND faster recovery)."""

    probe_interval: int = Field(16, ge=1)
    """At k=0 (acceptance collapsed), propose a single probe draft every this
    many decode steps so acceptance can recover when the text turns
    repetitive again."""

    draft_token_budget: Optional[int] = Field(None, ge=1)
    """Cap on draft tokens per batch (they compete with prefill chunks under
    the ragged token budget); None = bounded only by that budget. Brownout
    stage >= 2 zeroes the budget regardless."""

    @model_validator(mode="after")
    def _ngram_ordered(self):
        if self.max_ngram < self.min_ngram:
            raise ValueError("max_ngram must be >= min_ngram")
        return self


class KVTierConfig(DeepSpeedConfigModel):
    """Tiered KV memory (``inference/v2/ragged/tiering.py`` +
    ``serving/kv_tiers.py``): device blocks → host memory → disk spill files.
    Off by default — when enabled, KV pressure *demotes* cached-but-idle
    state down the ladder (prefix-trie nodes first, then offloaded sessions
    host→disk) before anything is evicted or shed, and brownout gains a
    demote stage ahead of shedding."""

    enabled: bool = False
    """Run the tiered ladder: configure the engine's tiered store with the
    budget/spill policy below and demote under pressure."""

    host_bytes: Optional[int] = Field(None, ge=0)
    """Host-tier budget in bytes: when host-resident offloaded KV exceeds it
    (and ``spill_dir`` is set), the coldest entries demote to disk on the
    async writer. None = unbounded host tier."""

    spill_dir: Optional[str] = None
    """Disk-tier directory for spill files; None = the host tier is the
    floor (nothing demotes to disk)."""

    demote_batch: int = Field(4, ge=1)
    """Device blocks demoted per pressure tick (brownout's demote-before-shed
    stage and the scheduler's demote-first eviction)."""


class OverloadConfig(DeepSpeedConfigModel):
    """Overload control (``serving/overload.py``): priority admission,
    deadline-aware shedding and staged brownout degradation. Enabled by
    default but quiescent under normal load — admission control only acts on
    requests that carry a deadline, and the brownout stages only engage when
    the smoothed pressure signal clears the thresholds."""

    enabled: bool = True
    """Master switch. False = the pre-overload-control scheduler: FIFO queue
    order, no admission estimate, no shedding, no brownout (the uniform-FIFO
    control arm the overload gates compare against)."""

    priority_ordering: bool = True
    """Admit queued requests in (priority, deadline, arrival) order instead
    of FIFO; within a class, earliest deadline first."""

    admission_control: bool = True
    """Estimate queue wait from the measured token rate at ``submit()`` and
    reject a request whose deadline is provably unmeetable (HTTP 429 +
    ``Retry-After``) instead of admitting it to fail mid-queue — rejecting at
    admission is cheap, failing after prefill wastes engine work."""

    admission_margin: float = Field(1.0, gt=0)
    """Feasibility proof margin: a request is rejected when the estimated
    completion time exceeds ``deadline * margin``. Values above 1 are more
    lenient (reject later); below 1 more aggressive."""

    min_rate_samples: int = Field(4, ge=1)
    """Executed batches the rate estimator needs before admission control or
    shedding trusts it; a cold estimator admits everything."""

    rate_alpha: float = Field(0.25, gt=0, le=1)
    """EWMA smoothing factor for the measured token rate."""

    shed_enabled: bool = True
    """Under sustained pressure (brownout stage >= 1), shed queued requests
    whose deadline is provably unmeetable — lowest priority / latest deadline
    first — before they waste a prefill."""

    brownout_stage_thresholds: Tuple[float, float, float] = (0.65, 0.85, 0.95)
    """Smoothed-pressure entry thresholds for brownout stages 1..3 (stage 1:
    clamp batch ``max_new_tokens``; stage 2: + disable speculative decode
    chunking; stage 3: + reject batch class at submission)."""

    brownout_hysteresis: float = Field(0.1, ge=0)
    """A stage entered at threshold ``t`` is only left when the smoothed
    pressure falls below ``t - hysteresis`` (no service-mode flapping)."""

    pressure_alpha: float = Field(0.3, gt=0, le=1)
    """EWMA smoothing factor for the pressure signal
    (``max(queue_fraction, kv_occupancy)``, sampled every scheduler tick)."""

    brownout_clamp_max_new_tokens: int = Field(16, ge=1)
    """Stage >= 1 generation cap for batch-class requests (flagged
    ``degraded_mode`` in the response)."""

    retry_after_floor_s: float = Field(0.5, gt=0)
    retry_after_cap_s: float = Field(30.0, gt=0)
    """Bounds on the ``Retry-After`` estimate derived from the measured queue
    drain rate (429/503 responses)."""

    slo_pressure: bool = False
    """Feed the SLO engine's breach signal (fast-window burn normalized by
    its alert threshold, in [0, 1]) into the brownout pressure sample as a
    floor — a burning error budget browns the replica out even while queue
    depth and KV occupancy look healthy. Requires an active telemetry
    session with ``telemetry.slo`` configured; off by default."""

    fair_share_enabled: bool = False
    """Tenant fair-share stage in the admission path (opt-in): while the
    brownout controller reports pressure (stage >= 1), a tenant whose
    measured share of the token rate exceeds ``fair_share_over_factor`` x its
    configured share is shed first — new submissions 429 with ``Retry-After``
    and its queued requests are shed ahead of deadline-based shedding
    (deficit-weighted). Requires ``enabled``; the ``enabled=false`` control
    arm is untouched."""

    fair_share_shares: Optional[Dict[str, float]] = None
    """Per-tenant share weights (normalized over tenants seen); None = equal
    split across every tenant that has submitted. Tenants missing from the
    map get weight 1.0."""

    fair_share_alpha: float = Field(0.2, gt=0, le=1)
    """EWMA smoothing for per-tenant measured token rates."""

    fair_share_over_factor: float = Field(1.25, gt=1)
    """A tenant is over-share when measured share > factor x configured
    share."""

    fair_share_hysteresis: float = Field(0.25, ge=0)
    """The over-share verdict clears only below
    ``(over_factor - hysteresis) x configured share`` (no admit/shed
    flapping at the boundary)."""

    @model_validator(mode="after")
    def _ordered_thresholds(self):
        if list(self.brownout_stage_thresholds) != sorted(self.brownout_stage_thresholds):
            raise ValueError("brownout_stage_thresholds must be ascending")
        return self


class CostConfig(DeepSpeedConfigModel):
    """Cost-attribution plane (``telemetry/ledger.py`` + ``perf/observed.py``):
    per-request metering, bounded per-tenant rollups (``/v1/usage``), and the
    predicted-vs-observed perf ledger. The plane only materializes while a
    telemetry session is active — with telemetry off every hot-path site is a
    single None check and the registry sees zero api_calls."""

    enabled: bool = True
    """Meter requests when telemetry is active. False = no ledger even with
    telemetry on (spans/metrics still record)."""

    default_tenant: str = "default"
    """Tenant billed for requests that carry no identity (no ``tenant`` JSON
    field, no ``X-DSTPU-Tenant`` header)."""

    max_tenants: int = Field(64, ge=1)
    """Bound on distinct tenants in the usage rollup; later tenants fold
    into ``<other>`` (sums still reconcile against the aggregate)."""

    tenant_metric_top_k: int = Field(8, ge=1)
    """Bound on per-tenant metric label sets (``serving_tenant_*``); tenants
    past the cap share the ``<other>`` label."""

    perf_chip: str = "v5e"
    """Chip spec the observed-vs-predicted join prices rooflines against
    (``perf/chip_specs.py``); drift detection is baseline-relative, so an
    off-target chip only shifts the absolute ratio, not the alarm."""

    perf_drift_factor: float = Field(4.0, gt=1)
    """Observed/predicted ratio above ``factor x baseline`` counts toward a
    drift episode."""

    perf_drift_consecutive: int = Field(3, ge=1)
    """Consecutive over-factor dispatches that raise one drift event."""

    perf_baseline_dispatches: int = Field(8, ge=1)
    """Post-amnesty dispatches averaged into each (program, bucket)'s
    baseline ratio before drift detection arms."""


class ServingConfig(DeepSpeedConfigModel):
    """Knobs for the request scheduler + HTTP front-end."""

    queue_capacity: int = Field(128, ge=1)
    """Maximum QUEUED (admitted-but-unscheduled) requests; beyond it the
    backpressure policy applies."""

    backpressure: Literal["reject", "block"] = "reject"
    """Queue-full behavior: ``reject`` fails ``submit()`` immediately (HTTP
    429); ``block`` stalls the submitting thread until space frees (the
    closed-loop client pattern)."""

    default_max_new_tokens: int = Field(64, ge=1)
    """Per-request cap when the request doesn't specify one."""

    default_deadline_s: Optional[float] = Field(None, gt=0)
    """Deadline applied to requests that don't carry their own; None = no
    deadline (requests are bounded by max_new_tokens only)."""

    drain_timeout_s: float = Field(30.0, ge=0)
    """Graceful-shutdown budget: how long ``stop(drain=True)`` lets in-flight
    requests finish before cancelling the remainder."""

    scheduler_tick_s: float = Field(0.001, gt=0)
    """Idle sleep between scheduler iterations when there is no work; busy
    iterations run back-to-back."""

    decode_chunk: int = Field(1, ge=1)
    """Decode steps per device dispatch on the decode-only fast path
    (``engine.decode_loop``); >1 trades up-to-(K-1)-token speculative
    over-generation for one host round-trip per K tokens."""

    max_prefill_chunk: Optional[int] = Field(None, ge=1)
    """Cap on prompt tokens admitted per batch per request (Dynamic SplitFuse
    chunk size); None = bounded only by the engine's ragged token budget."""

    heartbeat_interval_s: float = Field(0.05, ge=0)
    """How often an *idle* scheduler runs ``engine.empty_run()`` so EP
    replicas stay in collective lock-step. 0 = every idle tick."""

    heartbeat_enabled: Optional[bool] = None
    """None = auto (heartbeat only when the engine has expert parallelism
    enabled); True/False force it."""

    sse_keepalive_s: float = Field(10.0, gt=0)
    """SSE comment-line cadence while a stream has no token to send (queue
    wait, long prefill): keeps the socket demonstrably alive so a fleet
    router's bounded read budget (``FleetConfig.read_timeout_s``) measures
    replica *death*, never mere load."""

    host: str = "127.0.0.1"
    port: int = Field(0, ge=0, le=65535)
    """Bind address for ``ServingServer``; port 0 = ephemeral (the bound
    address is on ``server.address`` after ``start()``)."""

    prefix_cache: PrefixCacheConfig = PrefixCacheConfig()
    """Automatic prefix caching over the paged KV cache (radix-tree reuse +
    copy-on-write sharing); see :class:`PrefixCacheConfig`."""

    speculative: SpeculativeConfig = SpeculativeConfig()
    """Speculative decoding (model-free self-drafting + batch-wide verify);
    see :class:`SpeculativeConfig`."""

    overload: OverloadConfig = OverloadConfig()
    """Overload control: priority admission, deadline-aware shedding, staged
    brownout degradation; see :class:`OverloadConfig`."""

    kv_tiers: KVTierConfig = KVTierConfig()
    """Tiered KV memory (device→host→disk demotion under pressure); see
    :class:`KVTierConfig`."""

    cost: CostConfig = CostConfig()
    """Cost-attribution plane: per-request/per-tenant metering ledger and the
    predicted-vs-observed perf ledger; see :class:`CostConfig`."""

    max_resume_body_bytes: int = Field(DEFAULT_MAX_RESUME_BODY_BYTES, gt=0)
    """Upper bound on a ``POST /v1/resume`` body (the base64 KV-handoff
    payload; real-model KV runs to hundreds of MB and base64 adds 4/3). The
    body is fully buffered per handler thread, so operators whose resume
    endpoint is reachable beyond fleet-internal traffic should lower this to
    their largest expected payload."""

    @field_validator("default_deadline_s")
    @classmethod
    def _deadline_finite(cls, v):
        if v is not None and not (v > 0 and v == v):  # rejects NaN too
            raise ValueError("default_deadline_s must be a positive number")
        return v
