"""Runtime/launcher layer (≈ the reference's ORTE, orte/).

Job launch and process wire-up, heavily simplified for the TPU world where
"nodes" are usually TPU hosts and a TPU host has one slot (one rank drives
all its chips):

- ``job``     — Job/Node/Proc data model (≈ orte_job_t/orte_node_t/orte_proc_t,
                orte/runtime/orte_globals.h:215-342).
- ``state``   — event-driven job state machine; the launch DAG is data, not
                code (≈ orte/mca/state/hnp/state_hnp.c:74-112).
- ``ras``     — resource allocation framework: localhost, simulator (fake
                clusters for tests, ≈ orte/mca/ras/simulator), tpu (one
                slot a host; never touches jax).
- ``rmaps``   — proc→node/slot mapping and ranking (round_robin, ppr, seq).
- ``pmix``    — rendezvous/modex service: put/get/fence business-card exchange
                (≈ opal/mca/pmix; the launcher hosts the server, app procs are
                clients).
- ``errmgr``  — failure response policy (≈ orte/mca/errmgr).
- ``launcher``— fork/exec of app procs with IOF forwarding and the error-pipe
                protocol (≈ orte/mca/odls/default + orte/mca/iof).
"""
