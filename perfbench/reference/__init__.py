"""Plain references of the benchmark's configurations, and the writer of
the goldens made from them.

``kimi_vl_a3b_lm.py`` is the repository's ONE copy of that reference: the
goldens that decide ``correct`` in the ``kimi-vl-a3b-lm-bf16`` cells are
written from it (``write_golden.py``), and the program's own tests
(``tests/test_mla_moe.py``) hold the served path to the same file. It lives
here, under the benchmark's ``paths``, so that a change to it is a change
to the yardstick that a review sees as one."""
