"""The host side both scoring engines share (scoring/settle.py): a
take's occurrence rounds, score placement for the full and the sparse
read-back, the flight book when a settle fails, and the statistics a
model declares, which the session feeds without knowing their names."""

import asyncio
import threading
from concurrent.futures import ThreadPoolExecutor

import jax.numpy as jnp
import numpy as np
import pytest

from sitewhere_tpu.domain.batch import BatchContext, MeasurementBatch
from sitewhere_tpu.kernel.metrics import Histogram, MetricsRegistry
from sitewhere_tpu.kernel.tracing import Tracer
from sitewhere_tpu.models import build_model
from sitewhere_tpu.persistence.telemetry import TelemetryStore
from sitewhere_tpu.scoring.server import ScoringConfig, ScoringSession
from sitewhere_tpu.scoring.settle import (
    Flights,
    anomalous_subset,
    occurrence_rounds,
    place_scores,
)

# -- occurrence rounds ---------------------------------------------------------

# (ids, the rounds the engines split them into before the split was
# shared: (ids, values, positions in the take) each, values = 10 x id +
# position)
TAKES = {
    "ascending": ([1, 4, 7], [([1, 4, 7], [10.0, 41.0, 72.0], None)]),
    "unsorted": ([5, 2, 9, 0],
                 [([0, 2, 5, 9], [3.0, 21.0, 50.0, 92.0], [3, 1, 0, 2])]),
    "repeats": ([3, 1, 3, 2, 1, 3],
                [([1, 2, 3], [11.0, 23.0, 30.0], [1, 3, 0]),
                 ([1, 3], [14.0, 32.0], [4, 2]),
                 ([3], [35.0], [5])]),
}


@pytest.mark.parametrize("kind", sorted(TAKES))
def test_occurrence_rounds_are_the_rounds_the_engines_dispatched(kind):
    ids, want = TAKES[kind]
    dev = np.asarray(ids, np.int32)
    val = (dev * 10 + np.arange(dev.shape[0])).astype(np.float32)
    rounds, ascending = occurrence_rounds(dev, val)
    assert ascending == (kind == "ascending")
    got = [(r.tolist(), v.tolist(), None if p is None else p.tolist())
           for r, v, p in rounds]
    assert got == want
    if ascending:
        # the take as it stands: no copy of a column
        assert rounds[0][0] is dev and rounds[0][1] is val


# -- score placement -----------------------------------------------------------


def test_full_scores_go_to_their_positions_in_the_take():
    """The repeats take above: three rounds, each read back padded to
    its bucket; a round's first `k` scores land at its positions."""
    rounds = [(np.array([1.0, 3.0, 0.0, -1.0]), 3, np.array([1, 3, 0])),
              (np.array([4.0, 2.0, -1.0, -1.0]), 2, np.array([4, 2])),
              (np.array([5.0, -1.0]), 1, np.array([5]))]
    assert place_scores(6, rounds).tolist() == [0.0, 1.0, 2.0, 3.0, 4.0,
                                                 5.0]
    ascending = [(np.array([7.0, 8.0, -1.0]), 2, None)]
    assert place_scores(2, ascending).tolist() == [7.0, 8.0]


def test_sparse_anomalies_are_rebuilt_across_rounds_and_overflow_counted():
    """Round 0 reports two of its three events; round 1 had four
    anomalies and room for two (overflow 2); round 2's one slot names a
    padding position, which is dropped."""
    overflow = MetricsRegistry().counter("scoring.anomaly_overflow")
    rounds = [((2, np.array([0, 2]), np.array([5.0, 6.0], np.float16)), 3,
               np.array([1, 3, 0])),
              ((4, np.array([1, 0]), np.array([7.0, 8.0], np.float16)), 2,
               np.array([4, 2])),
              ((1, np.array([3]), np.array([9.0], np.float16)), 1, None)]
    found, scores = anomalous_subset(rounds, overflow)
    assert found.tolist() == [1, 0, 2, 4]
    assert scores.dtype == np.float32
    assert scores.tolist() == [5.0, 6.0, 7.0, 8.0]
    assert overflow.value == 2
    found, scores = anomalous_subset([((0, np.array([0]), np.array([1.0])),
                                       4, None)], overflow)
    assert found.shape == scores.shape == (0,)


# -- the flight book ---------------------------------------------------------


class _Held:
    """A device result whose read-back waits for `gate`."""

    def __init__(self, gate: threading.Event):
        self.gate = gate

    def __array__(self, dtype=None, copy=None):
        self.gate.wait(10.0)
        return np.zeros(4, np.float32)


class _Lost:
    """A device result whose read-back fails."""

    def __array__(self, dtype=None, copy=None):
        raise RuntimeError("device lost")


def test_a_failed_settle_is_dropped_and_leaves_the_book(run):
    """Dispatch 0 settles late, dispatch 1's read-back fails: 1's events
    count as dropped, its caller's future hears the failure, its release
    runs, and the commit barrier waits for 0 and then passes both; no
    settle task is left behind."""
    metrics = MetricsRegistry()
    flights = Flights(metrics, Tracer(metrics=metrics))
    gate = threading.Event()
    released = []

    def assemble(settled, now):
        return [("t", [], None, None)]

    async def main():
        with ThreadPoolExecutor(max_workers=2) as pool:
            flights.launch(pool, [_Held(gate)], 4, 0.0, 0.0, assemble)
            fut = asyncio.get_running_loop().create_future()
            flights.launch(pool, [_Lost()], 3, 0.0, 0.0, assemble, fut,
                           release=lambda: released.append(1))
            with pytest.raises(RuntimeError, match="device lost"):
                await fut
            await asyncio.sleep(0)
            assert flights.dropped.value == 3 and released == [1]
            assert (flights.inflight, flights.settled_count,
                    flights.settled_through) == (1, 1, 0)
            gate.set()
            while flights.tasks:
                await asyncio.sleep(0.01)
        assert (flights.inflight, flights.dispatch_count,
                flights.settled_count, flights.settled_through) == (0, 2, 2, 2)
        assert flights.dropped.value == 3

    run(main())


# -- what a model says of its steps ------------------------------------------


class _Probe:
    """The least streaming model that says something of its steps: a
    window leaf of 8 positions, the score an event's value, and one
    number of a name no engine knows, the step's live rows."""

    streaming = True
    windows = {"far": "pos"}
    step_stats = ("probe.live_rows",)

    class cfg:
        window = 4

    def init(self, rng):
        return {"one": jnp.ones(())}

    def init_state(self, cap):
        return {"pos": jnp.zeros(cap, jnp.int32),
                "far": jnp.zeros((cap, 8, 128), jnp.float32)}

    def score(self, params, x, valid):
        return x[:, -1] * params["one"]

    def step_score(self, params, rows, v, live):
        entry = jnp.broadcast_to(v[:, None], (v.shape[0], 128))
        return (v * params["one"], {"pos": rows["pos"] + 1, "far": entry},
                jnp.stack([live.sum()]))

    def warm_state(self, params, x, valid):
        state = self.init_state(x.shape[0])
        state["pos"] = valid.sum(1).astype(jnp.int32)
        return state

    def stat_feeds(self, metrics):
        return [metrics.counter("scoring.probe.live_rows").inc], []


def test_a_statistic_of_a_new_name_is_fed_as_the_model_declares_it(run):
    metrics = MetricsRegistry()
    session = ScoringSession(_Probe(), TelemetryStore(history=16), metrics,
                             ScoringConfig(buckets=(8,), capacity=4,
                                           score_dtype="float32"))
    session.warmup()

    async def main():
        for n, t in ((3, 60.0), (2, 120.0)):
            batch = MeasurementBatch(
                BatchContext(tenant_id="t"), np.arange(n, dtype=np.uint32),
                np.zeros(n, np.uint16), np.full(n, 2.5, np.float32),
                np.full(n, t))
            session.admit(batch)
            assert (await session.flush()).score.tolist() == [2.5] * n

    run(main())
    assert metrics.counter("scoring.probe.live_rows").value == 5
    session.close()


OCTAVES = [2.0 ** (i / 4) for i in range(53)]
DECAY = [i / 64 for i in range(1, 65)]
ABSMAX = [2.0 ** (i / 4) for i in range(-96, 33)]
MOE = [("scoring.moe.assignments_held", "Counter", None),
       ("scoring.moe.assignments", "Counter", None),
       ("scoring.moe.expert_max_tokens", "Histogram", OCTAVES),
       ("scoring.ctx.positions", "Histogram", OCTAVES),
       ("scoring.moe.runs_one_tile", "Counter", None)]
AT_REST = ("scoring.ctx.at_rest_rows", "Counter", None)
READ = ("scoring.ctx.read_positions", "Counter", None)
WEIGHTS = ("scoring.moe.weight_bytes", "Counter", None)

# what the session registered for each sequence model's step, in the
# order of its `step_stats`, before the models declared it (and, last,
# the counter fed once a dispatch for the models that hold experts);
# beside the in-place rows, the bytes the state kernel moves for them,
# and the model with Mamba-2 layers, whose step declares all three
# families; the positions the context kernel copied, of every model but
# the one whose latent context it reads in its one-table form
REGISTERED = {
    "dsv3-stream": MOE + [AT_REST, WEIGHTS],
    "laguna-stream": MOE + [
        ("scoring.ctx.window_positions", "Histogram", OCTAVES),
        ("scoring.ctx.wrapped", "Counter", None), AT_REST, READ, WEIGHTS],
    "lfm2-stream": MOE + [AT_REST, READ, WEIGHTS],
    "olmo-hybrid-stream": [
        ("scoring.ctx.positions", "Histogram", OCTAVES),
        ("scoring.state.decay", "Histogram", DECAY),
        ("scoring.state.absmax", "Histogram", ABSMAX),
        ("scoring.state.in_place_rows", "Counter", None),
        ("scoring.state.kernel_bytes", "Counter", None), AT_REST, READ],
    "nemotron-h-stream": MOE + [
        AT_REST, READ, ("scoring.state.decay", "Histogram", DECAY),
        ("scoring.state.absmax", "Histogram", ABSMAX),
        ("scoring.state.in_place_rows", "Counter", None),
        ("scoring.state.kernel_bytes", "Counter", None), WEIGHTS],
    "ouro-stream": [
        ("scoring.ctx.positions", "Histogram", OCTAVES), AT_REST,
        ("scoring.loop.weight_bytes", "Counter", None),
        ("scoring.ctx.attended_bytes", "Counter", None), READ],
}


@pytest.mark.parametrize("name", sorted(REGISTERED))
def test_each_sequence_models_declarations_register_what_the_session_did(
        name):
    from tests.test_dsv3 import MC as DSV3
    from tests.test_laguna import MC as LAGUNA
    from tests.test_lfm2 import MC as LFM2
    from tests.test_nemotron_h import MC as NEMOTRON
    from tests.test_olmo_hybrid import MC as OLMO
    from tests.test_ouro import MC as OURO

    widths = {"dsv3-stream": DSV3, "laguna-stream": LAGUNA,
              "lfm2-stream": LFM2, "olmo-hybrid-stream": OLMO,
              "ouro-stream": OURO, "nemotron-h-stream": NEMOTRON}[name]
    model = build_model(name, **widths)
    metrics = MetricsRegistry()
    per_step, per_dispatch = model.stat_feeds(metrics)
    assert len(per_step) == len(model.step_stats)
    assert len(per_dispatch) == (REGISTERED[name][-1] == WEIGHTS)
    got = [(metric, type(m).__name__,
            m.buckets if isinstance(m, Histogram) else None)
           for metric, m in metrics._metrics.items()]
    assert got == REGISTERED[name]
