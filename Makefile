# Tier-1 verification + dev conveniences.
#
#   make install      editable install of src/repro (replaces the PYTHONPATH=src hack)
#   make test         tier-1 test suite

PY ?= python

.PHONY: install test

install:
	$(PY) -m pip install -e .

test:
	PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH} $(PY) -m pytest -x -q
