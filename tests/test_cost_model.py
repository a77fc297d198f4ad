"""Direct unit coverage for serving/cost_model.py (previously exercised
only indirectly through the engine): analytic construction, real measured
steps on the smallest config, degree selection, and the error contract."""
import pytest

from repro.configs import get_smoke_config
from repro.serving.cost_model import (
    CostModel,
    PhaseCost,
    analytic_cost_model,
    measure_cost_model,
)

EFF_RATIO = 11.611 / 16.862


# --------------------------------------------------------------------- #
# analytic_cost_model                                                   #
# --------------------------------------------------------------------- #
def test_analytic_cost_model_shape_and_std_frac():
    cm = analytic_cost_model({2: 0.4, 4: 0.25, 8: 0.18},
                             prefill_s=0.9, std_frac=0.1)
    assert cm.degrees == (2, 4, 8)
    assert cm.prefill[1].mean_s == 0.9
    assert cm.prefill[1].std_s == pytest.approx(0.09)
    for deg, t in {2: 0.4, 4: 0.25, 8: 0.18}.items():
        assert cm.decode[deg].mean_s == t
        assert cm.decode[deg].std_s == pytest.approx(t * 0.1)
        assert cm.decode[deg].padded == pytest.approx(t * 1.1)


def test_analytic_cost_model_default_std_frac():
    cm = analytic_cost_model({2: 1.0}, prefill_s=0.5)
    assert cm.decode[2].std_s == pytest.approx(0.05)


# --------------------------------------------------------------------- #
# error contract: unknown degrees raise ValueError naming the options   #
# --------------------------------------------------------------------- #
def _synthetic() -> CostModel:
    cm = CostModel()
    cm.prefill[1] = PhaseCost(0.05, 0.005)
    cm.decode[2] = PhaseCost(0.02, 0.002)
    cm.decode[4] = PhaseCost(0.014, 0.0014)
    return cm


def test_lp_exec_time_unknown_degree_lists_available():
    cm = _synthetic()
    with pytest.raises(ValueError, match=r"degree 3.*\[2, 4\]"):
        cm.lp_exec_time(3, 10)
    with pytest.raises(ValueError, match=r"\[2, 4\]"):
        cm.lp_slot_time(8, 10)


def test_hp_exec_time_unknown_degree_lists_available():
    cm = _synthetic()
    with pytest.raises(ValueError, match=r"degree 2.*\[1\]"):
        cm.hp_exec_time(2)
    with pytest.raises(ValueError, match="prefill"):
        cm.hp_slot_time(4)


def test_empty_cost_model_error_message():
    with pytest.raises(ValueError, match="none"):
        CostModel().lp_exec_time(2, 1)


# --------------------------------------------------------------------- #
# measure_cost_model: real timed steps on the smallest config           #
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def measured():
    cfg = get_smoke_config("smollm-135m")
    return measure_cost_model(cfg, prompt_len=8, cache_len=16, reps=1)


def test_measure_cost_model_smallest_config(measured):
    assert measured.degrees == (2, 4)
    assert measured.prefill[1].mean_s > 0.0
    assert measured.decode[2].mean_s > 0.0
    # paper-calibrated efficiency curve anchors degree 4 off degree 2
    assert measured.decode[4].mean_s == pytest.approx(
        measured.decode[2].mean_s * EFF_RATIO)
    assert measured.decode[4].std_s == pytest.approx(
        measured.decode[2].std_s * EFF_RATIO)


def test_measure_cost_model_honors_degrees():
    cfg = get_smoke_config("smollm-135m")
    cm = measure_cost_model(cfg, prompt_len=8, cache_len=16, reps=1,
                            degrees=(2, 8))
    assert cm.degrees == (2, 8)
    # two doublings from the degree-2 anchor
    assert cm.decode[8].mean_s == pytest.approx(
        cm.decode[2].mean_s * EFF_RATIO ** 2)


@pytest.mark.parametrize("bad", [(), (0,), (2, 2), (2, -4), (2.5,)])
def test_measure_cost_model_rejects_bad_degrees(bad):
    cfg = get_smoke_config("smollm-135m")
    with pytest.raises(ValueError):
        measure_cost_model(cfg, prompt_len=8, cache_len=16, reps=1,
                           degrees=bad)


def test_cost_model_times_the_engines_own_donated_steps(monkeypatch):
    """The cost model and the engine build their steps with one helper, so
    the cost model times the serve step that donates its caches; its decode
    reps thread each returned cache into the next (a donated cache handed
    in again would raise)."""
    import jax

    from repro.models import model as M
    from repro.serving import engine as E

    made, real = [], E.jit_serving_steps

    def spy(cfg, cache_len):
        made.append(cache_len)
        return real(cfg, cache_len)

    monkeypatch.setattr(E, "jit_serving_steps", spy)
    cfg = get_smoke_config("smollm-135m")
    cm = measure_cost_model(cfg, prompt_len=8, cache_len=16, reps=3)
    assert cm.decode[2].mean_s > 0.0 and cm.prefill[1].mean_s > 0.0
    E.PreemptiveServingEngine(cfg, M.init_params(cfg, jax.random.PRNGKey(0)),
                              cm, cache_len=16)
    assert made == [16, 16]
