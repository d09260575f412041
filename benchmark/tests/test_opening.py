"""When the measured window opens: on the boundary first aimed at while the
compiler is quiet, and a slide or more later when a program compiles on the
way there, so that no compile lands inside the window."""

from harness import runner
from test_program_spans import rehearse

# one cell a test, and none that test_program_spans rehearses: the program's
# counters belong to the job, which is named after the cell, for as long as
# the process lives


def test_a_quiet_warm_up_opens_on_the_boundary_first_aimed_at(tmp_path, monkeypatch, capsys):
    line, run = rehearse("q5-sat", 2.0, tmp_path, monkeypatch, capsys)
    opening = run["opening"]
    assert opening["aims"] == 1 and opening["boundary"] == opening["first_aimed_boundary"]
    assert opening["event"] == opening["boundary"] * 400  # 2 s slides of 5 ms events
    # set-up's own compiles came before, and the line says how long before
    assert opening["last_compile"]["ended_s_before_opening"] > 0
    assert line["compared"]["compiles_in_window"]["value"] == 0


def test_a_compile_on_the_way_to_the_boundary_opens_later(tmp_path, monkeypatch, capsys):
    import jax
    import jax.numpy as jnp

    boundary_time, injected = runner.Run._boundary_time, []

    def compiling_once(self, b):
        at = boundary_time(self, b)
        if not injected and self.opening is None:
            # the harness has aimed at b and asks whether the scans are there:
            # a program the process has not met compiles just now
            injected.append(b)
            jax.block_until_ready(jax.jit(lambda x: x * 3 + b)(jnp.arange(7)))
        return at

    monkeypatch.setattr(runner.Run, "_boundary_time", compiling_once)
    line, run = rehearse("q7-minute-sat", 2.0, tmp_path, monkeypatch, capsys)
    opening = run["opening"]
    assert injected == [opening["first_aimed_boundary"]]
    assert opening["aims"] == 2 and opening["boundary"] > opening["first_aimed_boundary"]
    assert "lambda" in opening["last_compile"]["program"]
    assert opening["last_compile"]["ended_s_before_opening"] > 0
    # on the boundary first aimed at, that compile would have ended inside
    assert line["compared"]["compiles_in_window"]["value"] == 0
