"""``ops/kernel_forms.py``: the one record of the forms a process traced its
kernel families' calls in, and the one refusal log."""

import logging

from ml_recipe_tpu.ops import kernel_forms


def test_a_snapshot_tells_what_was_traced_after_it():
    """``traced(since=...)`` holds the families and forms that gained a
    count after the snapshot, by how much; a form or family that gained
    nothing is left out, and a family first seen after it counts from 0."""
    kernel_forms.count("test_family_a", "kernel", 2)
    kernel_forms.count("test_family_a", "xla")
    before = kernel_forms.traced()
    assert before["test_family_a"] == {"kernel": 2, "xla": 1}
    kernel_forms.count("test_family_a", "kernel", 3)
    kernel_forms.count("test_family_b", "walked", 7)
    added = kernel_forms.traced(since=before)
    assert added["test_family_a"] == {"kernel": 3}
    assert added["test_family_b"] == {"walked": 7}
    assert kernel_forms.traced(since=kernel_forms.traced()) == {}
    # the record is a copy: the caller's snapshot does not move
    assert before["test_family_a"] == {"kernel": 2, "xla": 1}


def test_a_refusal_is_logged_once_a_module_and_reason(caplog):
    """Every layer of a trunk traces the same shapes: one warning a
    (module, reason), under the refusing module's logger."""
    one = logging.getLogger("ml_recipe_tpu.ops.test_one")
    two = logging.getLogger("ml_recipe_tpu.ops.test_two")
    kernel_forms.log_refusal.cache_clear()
    with caplog.at_level(logging.WARNING, "ml_recipe_tpu.ops"):
        for _ in range(3):
            kernel_forms.log_refusal(one, "one: refused (a)")
            kernel_forms.log_refusal(one, "one: refused (b)")
            kernel_forms.log_refusal(two, "one: refused (a)")
    assert [(r.name, r.getMessage()) for r in caplog.records] == [
        ("ml_recipe_tpu.ops.test_one", "one: refused (a)"),
        ("ml_recipe_tpu.ops.test_one", "one: refused (b)"),
        ("ml_recipe_tpu.ops.test_two", "one: refused (a)")]
    kernel_forms.log_refusal.cache_clear()
