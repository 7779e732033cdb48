"""Traffic kinds. A kind module has:

``E2E``: {end-to-end metric name: unit} it reports;
``plan(params, config, seed, seconds)`` -> {"groups": [(due_s, n_pods)],
"threads": senders, "deadline_s": seconds after the origin at which the
run gives up on what is unbound};
``metrics(obs)`` -> {name: value} from the client-side observation:
``obs["seconds"]``, ``obs["deadline_s"]``, ``obs["due"]`` (per measured
pod, seconds after the origin at which it was due) and ``obs["bound"]``
(per measured pod, seconds after the origin at which the watcher saw it
bound and the store confirmed it, else None).
"""
