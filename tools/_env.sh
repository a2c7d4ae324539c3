# Shared environment discipline for every hardware-facing tools/ script.
# Source AFTER cd'ing to the repo root.
#
# PYTHONPATH must carry the repo, and must be APPENDED to, never replaced.
export PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}"
