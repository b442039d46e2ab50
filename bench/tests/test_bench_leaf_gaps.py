"""The leaf gaps of `bench.correct` on readings worked by hand."""
import pytest

from bench import correct


def test_a_missing_leaf_is_an_infinite_gap():
    ref = {"loss_u": [1.0], "loss_v": [1.0],
           "grad": {"a": 1.0, "b": 2.0}, "change": {"a": 1.0, "b": 1.0},
           "first_step_leaves": ["a", "b"]}
    prog = {"loss_u": [1.0], "loss_v": [1.0],
            "grad": {"a": 1.0}, "change": {"a": 1.0, "b": 1.0}}
    assert correct.gaps(prog, ref)["grad_gap"] == float("inf")


def test_a_leaf_with_nought_gradient_is_left_out_of_the_change():
    ref = {"loss_u": [1.0], "loss_v": [1.0],
           "grad": {"a": 1.0, "b": 2.0, "bias": 1e-9},
           "change": {"a": 1.0, "b": 1.0, "bias": 1e-12},
           "first_step_leaves": ["a", "b", "bias"]}
    prog = dict(ref, change={"a": 1.0, "b": 1.0, "bias": 5.0})
    g = correct.gaps(prog, ref)
    assert g["left_out_of_change"] == ["bias"]
    assert g["change_gap"] == 0.0
    assert g["grad_gap"] == 0.0


def test_only_leaves_with_one_optimizer_step_give_the_first_gradient():
    ref = {"loss_u": [1.0], "loss_v": [1.0],
           "grad": {"shared": 1.0, "head": 2.0},
           "change": {"shared": 1.0, "head": 1.0},
           "first_step_leaves": ["head"]}
    prog = dict(ref, grad={"shared": 1.5, "head": 2.002})
    g = correct.gaps(prog, ref)
    assert g["grad_gap"] == abs(2.002 - 2.0) / 2.0
    assert g["worst"]["grad_gap"] == "head"


def test_first_step_leaves_follow_the_steps_of_each_part():
    from bench.reference import dfedpgp
    assert dfedpgp.first_step_leaves(["features/conv1", "classifier/w"],
                                     {"k_u": 5, "k_v": 1}) == \
        ["classifier/w"]
    assert dfedpgp.first_step_leaves(["embed", "lm_head"],
                                     {"k_u": 1, "k_v": 1}) == \
        ["embed", "lm_head"]


def test_a_small_leaf_left_unmoved_fails_only_the_move_gap():
    # the reference moves a norm scale a little, the program not at all
    ref = {"loss_u": [1.0], "loss_v": [1.0],
           "grad": {"w1": 1.0, "w2": 2.0, "ln": 0.2},
           "change": {"w1": 100.0, "w2": 120.0, "ln": 0.012},
           "first_step_leaves": ["w1", "w2", "ln"]}
    prog = dict(ref, change={"w1": 100.0, "w2": 120.0, "ln": 0.0})
    g = correct.gaps(prog, ref)
    assert g["change_gap"] == 0.012 / 100.0
    assert g["move_gap"] == 1.0 and g["worst"]["move_gap"] == "ln"
    double = dict(ref, change={"w1": 100.0, "w2": 120.0, "ln": 0.024})
    assert correct.gaps(double, ref)["move_gap"] == 1.0


def test_the_move_gap_of_a_leaf_that_barely_moves_is_floored():
    ref = {"loss_u": [1.0], "loss_v": [1.0],
           "grad": {"w1": 1.0, "w2": 2.0, "mu": 1.0},
           "change": {"w1": 100.0, "w2": 120.0, "mu": 0.0},
           "first_step_leaves": ["w1", "w2"]}
    prog = dict(ref, change={"w1": 100.0, "w2": 120.0, "mu": 1e-7})
    assert correct.gaps(prog, ref)["move_gap"] == pytest.approx(
        1e-7 / (1e-6 * 110.0))
