"""The benchmark's own tests (``bench/tests``: harness, spec, traffic,
output check, trace reduction, readers) as cases of the tier-1 command.

A ``benchmark`` PR may add files only under ``bench/``, so its tests live
there, outside ``testpaths``. ``tests/conftest.py`` collects this file as
the list of ``bench/tests/test_*.py``, each a module of its own (its own
fixtures, its own worker under ``--dist loadfile``), every test a counted
case. ``bench/tests/conftest.py`` is not loaded: the tests run on this
suite's eight virtual CPU devices (it asks for four; a cell takes the
first ``chips`` of what is there) and find ``benchlib`` and ``tiny``
through the two paths the hook appends.
"""
